"""Device seconds of the ops whose name (the whole HLO line) matches any
of the regular expressions ``args.ops``, as a share of the device's busy
seconds in the traced window (mean over devices)."""

from ..trace import op_seconds


def reduce(red, counters, cell):
    seconds, count = op_seconds(red, cell["spec"]["args"]["ops"])
    return 100.0 * seconds / red["busy_s"] if count else None
