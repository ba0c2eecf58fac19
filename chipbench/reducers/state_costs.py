"""A served model with state slots against the chip's peaks, by the
``costs`` and ``weights`` modules its configuration names (``modules``),
from its traced ``hvd.serve.decode`` spans (args: kind, span).  A span
without ``state_bytes`` (a program from before the slots, or a model
without them) gives None.

- ``decode_tick``: for each traced tick the least time (the larger of its
  FLOPs over peak and its least bytes over peak: every matrix, its
  tokens' embedding rows, the K and V of its ``kv_tokens``, its state
  slots' ``state_bytes``), over the device time of the decode program's
  runs (args: programs).
- ``ssd_kernel``: the traced ticks' live rows' states (``state_rows``),
  read and written in every layer, with the kernel's inputs and outputs,
  over the peak bandwidth (or its FLOPs over peak, if larger), over the
  decode kernel's device time (args: ops).
"""

import importlib

from ..program_spans import spans_of
from ..trace import module_runs, op_seconds


def reduce(red, counters, cell):
    args = cell["spec"]["args"]
    mods = cell["config"].get("modules")
    if not mods:
        return None
    ticks = [a for n, _, _, a in spans_of(red, cell)
             if n == args["span"] and "state_bytes" in a]
    if not ticks:
        return None
    costs = importlib.import_module("chipbench." + mods["costs"])
    weights = importlib.import_module("chipbench." + mods["weights"])
    d, pk = weights.dims_of(cell["config"]), cell["peaks"]
    if args["kind"] == "decode_tick":
        runs = module_runs(red, args["programs"])
        if not runs:
            return None
        least = sum(costs.least_seconds(*costs.tick_cost(
            d, a["state_rows"], a["kv_tokens"], a["state_bytes"]), pk)
            for a in ticks)
        return 100.0 * least / (sum(x for _, _, x in runs)
                                / len(red["devices"]))
    assert args["kind"] == "ssd_kernel", args["kind"]
    seconds, calls = op_seconds(red, args["ops"])
    if not calls:
        return None
    least = sum(costs.least_seconds(*costs.ssd_decode_cost(
        d, a["state_rows"]), pk) for a in ticks)
    return 100.0 * least / seconds
