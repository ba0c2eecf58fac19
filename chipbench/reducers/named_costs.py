"""A served model's work against the chip's peaks, by the ``costs`` and
``weights`` modules its configuration names (``modules``), over device
time of the traced window (args: kind).  Programs, the kernel and the
spans are found by name; a program that lacks them gives None.

- ``step_mfu``: FLOPs of the traced turns' first prefills and decode
  ticks over the bf16 peak, over the window.  A resumed request's second
  prefill is work done again and counts nothing.
- ``decode_tick``: the least time for each traced tick (the larger of
  its FLOPs over peak and its least bytes over peak), with the routed
  experts the tick's ``hvd.serve.decode`` span says it touched
  (``experts_touched``), over the device time of the decode program's
  runs (args: programs).
- ``mla_kernel``: for the traced ticks' streams in every layer, the
  larger of the live rows' published bytes over the peak bandwidth and
  the kernel's FLOPs over the peak, over the latent decode kernel's
  device time (args: ops).
"""

import importlib

from ..program_spans import spans_of
from ..trace import module_runs, op_seconds
from ._steps import traced_steps


def reduce(red, counters, cell):
    args = cell["spec"]["args"]
    mods = cell["config"].get("modules")
    steps = traced_steps(red, counters)
    if not steps or not mods:
        return None
    costs = importlib.import_module("chipbench." + mods["costs"])
    weights = importlib.import_module("chipbench." + mods["weights"])
    d, pk = weights.dims_of(cell["config"]), cell["peaks"]
    ticks = [s["decode"] for s in steps if s["decode"]]
    if args["kind"] == "step_mfu":
        flops = sum(costs.prefill_cost(d, p)[0]
                    for s in steps for p in s["prefill"]) \
            + sum(costs.decode_tick_cost(d, t)[0] for t in ticks)
        return 100.0 * flops / pk["flops_per_s"] / red["window_s"]
    if not ticks:
        return None
    if args["kind"] == "decode_tick":
        runs = module_runs(red, args["programs"])
        touched = [a["experts_touched"] for n, _, _, a in spans_of(red, cell)
                   if n == args["span"] and "experts_touched" in a]
        if not runs or not touched:
            return None
        # a span a tick where the trace's edges cut neither; else the mean
        per_tick = touched if len(touched) == len(ticks) else \
            [sum(touched) / len(touched)] * len(ticks)
        least = sum(costs.least_seconds(
            *costs.decode_tick_cost(d, t, e), pk)
            for t, e in zip(ticks, per_tick))
        return 100.0 * least / (sum(x for _, _, x in runs)
                                / len(red["devices"]))
    assert args["kind"] == "mla_kernel", args["kind"]
    seconds, calls = op_seconds(red, args["ops"])
    if not calls:
        return None
    least = sum(d["n_layers"] * costs.least_seconds(
        *costs.mla_decode_cost(d, t), pk) for t in ticks)
    return 100.0 * least / seconds
