"""The least time for the traced decode ticks' weights, KV pages and FLOPs
over the device time of the decode program's runs (args: programs)."""

from .. import costs, reference
from ..trace import module_runs
from ._steps import traced_steps


def reduce(red, counters, cell):
    ticks = [s["decode"] for s in traced_steps(red, counters) if s["decode"]]
    runs = module_runs(red, cell["spec"]["args"]["programs"])
    if not ticks or not runs:
        return None
    d, pk, bs = reference.dims_of(cell["config"]), cell["peaks"], \
        counters["block_size"]
    least = sum(costs.least_seconds(*costs.decode_tick_cost(d, t, bs), pk)
                for t in ticks)
    busy = sum(x for _, _, x in runs) / len(red["devices"])
    return 100.0 * least / busy
