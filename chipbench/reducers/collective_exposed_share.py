"""Seconds of collective ops on a device during which no other op runs
there, over the seconds of the step program's runs (mean over devices;
args: collectives, programs)."""

from ..trace import module_runs, union_length


def reduce(red, counters, cell):
    args = cell["spec"]["args"]
    lo, hi = red["lo"], red["hi"]
    is_coll = lambda n: any(p in n for p in args["collectives"])
    exposed = 0.0
    for dev in red["devices"]:
        coll = [(t, t + d) for dv, n, t, d in red["ops"]
                if dv == dev and is_coll(n)]
        rest = [(t, t + d) for dv, n, t, d in red["ops"]
                if dv == dev and not is_coll(n)]
        if not coll:
            return None
        both, _ = union_length(coll + rest, lo, hi)
        alone, _ = union_length(rest, lo, hi)
        exposed += both - alone
    step = sum(d for _, _, d in module_runs(red, args["programs"]))
    return 100.0 * exposed / step if step else None
