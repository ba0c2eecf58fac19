"""Median device milliseconds of one run of the named programs."""

import statistics

from ..trace import module_runs


def reduce(red, counters, cell):
    runs = module_runs(red, cell["spec"]["args"]["programs"])
    if not runs:
        return None
    return statistics.median(d for _, _, d in runs) * 1e3
