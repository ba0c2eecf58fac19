"""A looped decoder's work against the chip's peaks, every pass counted
(``costs_ouro``), over device time of the traced window (args: kind).

- ``step_mfu``: FLOPs of the traced turns' first prefills and decode
  ticks over the bf16 peak, over the window.  A resumed request's second
  prefill is work done again and counts nothing, as recomputation does
  not in a training step's share.
- ``decode_tick``: the least time for each traced tick (the larger of its
  FLOPs over peak and its least bytes over peak) over the device time of
  the decode program's runs (args: programs).
- ``paged_kernel``: the K and V pages the traced ticks' streams hold, in
  every cache layer, over the peak bandwidth, over the paged kernel's
  device time (args: ops).
"""

from .. import costs_ouro as costs, weights_ouro as weights
from ..trace import module_runs, op_seconds
from ._steps import traced_steps


def reduce(red, counters, cell):
    args = cell["spec"]["args"]
    steps = traced_steps(red, counters)
    if not steps:
        return None
    d, pk, bs = weights.dims_of(cell["config"]), cell["peaks"], \
        counters["block_size"]
    ticks = [s["decode"] for s in steps if s["decode"]]
    if args["kind"] == "step_mfu":
        flops = sum(costs.prefill_cost(d, p)[0]
                    for s in steps for p in s["prefill"]) \
            + sum(costs.decode_tick_cost(d, t, bs)[0] for t in ticks)
        return 100.0 * flops / pk["flops_per_s"] / red["window_s"]
    if not ticks:
        return None
    if args["kind"] == "decode_tick":
        runs = module_runs(red, args["programs"])
        if not runs:
            return None
        least = sum(costs.least_seconds(*costs.decode_tick_cost(d, t, bs),
                                        pk) for t in ticks)
        return 100.0 * least / (sum(x for _, _, x in runs)
                                / len(red["devices"]))
    assert args["kind"] == "paged_kernel", args["kind"]
    seconds, calls = op_seconds(red, args["ops"])
    if not calls:
        return None
    need = sum(costs.cache_layers(d) * costs.paged_decode_bytes(d, t, bs)
               for t in ticks)
    return 100.0 * need / pk["hbm_bytes_per_s"] / seconds
