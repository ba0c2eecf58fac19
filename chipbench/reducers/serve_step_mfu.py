"""The least time the chip could take for all prefill and decode work of
the traced turns (per program the larger of FLOPs over peak and bytes over
peak), over the traced window."""

from .. import costs, reference
from ._steps import traced_steps


def reduce(red, counters, cell):
    steps = traced_steps(red, counters)
    if not steps:
        return None
    d, pk, bs = reference.dims_of(cell["config"]), cell["peaks"], \
        counters["block_size"]
    least = 0.0
    for s in steps:
        for p in s["prefill"]:
            least += costs.least_seconds(*costs.prefill_cost(d, p), pk)
        if s["decode"]:
            least += costs.least_seconds(
                *costs.decode_tick_cost(d, s["decode"], bs), pk)
    return 100.0 * least / red["window_s"]
