"""A ratio of sums over the traced program spans of one name (args: span,
num, den).  ``num`` and ``den`` are lists of names whose product is taken
per span, each read from the span's attributes or, failing that, from the
run's counters: ["blocks"] over ["max_active", "n_cols"]."""

import math

from ..program_spans import spans_of


def reduce(red, counters, cell):
    args = cell["spec"]["args"]
    names = args["num"] + args["den"]
    num = den = 0.0
    for name, _, _, attrs in spans_of(red, cell):
        values = {**counters, **attrs}
        if name != args["span"] or any(n not in values for n in names):
            continue
        num += math.prod(values[n] for n in args["num"])
        den += math.prod(values[n] for n in args["den"])
    return 100.0 * num / den if den else None
