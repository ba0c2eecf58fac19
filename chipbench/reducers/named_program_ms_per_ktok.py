"""Device milliseconds of the named program per 1,000 of the tokens its
program spans name (args: programs, span, attr, skip_if; causal, the
triples that place the spans on the device's clock).  A program run
belongs to the span that covers most of it (the span ends with the fetch
of the program's result); runs and spans that the trace's edges cut apart
from their partner are left out on both sides."""

from ..program_spans import spans_of
from ..trace import module_runs


def reduce(red, counters, cell):
    args = cell["spec"]["args"]
    spans = [(t, t + d, a) for n, t, d, a in spans_of(red, cell)
             if n == args["span"] and args["attr"] in a
             and not a.get(args.get("skip_if"))]
    runs = sorted((t, t + d) for dv, t, d in module_runs(
        red, args["programs"]) if dv == red["devices"][0])
    seconds = tokens = 0.0
    for lo, hi, attrs in spans:
        inside = [e - s for s, e in runs
                  if min(e, hi) - max(s, lo) > 0.5 * (e - s)]
        if inside:
            seconds += sum(inside)
            tokens += attrs[args["attr"]]
    return seconds * 1e3 / (tokens / 1e3) if tokens else None
