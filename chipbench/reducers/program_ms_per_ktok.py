"""Device milliseconds of the named programs per 1,000 prompt tokens they
prefilled in the traced turns (args: programs)."""

from ..trace import module_runs
from ._steps import traced_steps


def reduce(red, counters, cell):
    runs = module_runs(red, cell["spec"]["args"]["programs"])
    tokens = sum(sum(s["prefill"]) for s in traced_steps(red, counters))
    if not runs or not tokens:
        return None
    n_dev = len(red["devices"])
    return sum(d for _, _, d in runs) / n_dev * 1e3 / (tokens / 1e3)
