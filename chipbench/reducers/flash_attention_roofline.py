"""The three flash kernels together against the MXU roofline: causal FLOPs
of every executed call (args.kernels maps fwd, dq, dkv to op-name
patterns) over the peak, over their device time."""

from .. import costs, reference
from ..trace import op_seconds


def reduce(red, counters, cell):
    args, tr = cell["spec"]["args"], cell["traffic"]
    d = reference.dims_of(cell["config"])
    per_call = costs.flash_flops(d, tr["rows_per_chip"],
                                 tr["sequence_length"])
    flops = seconds = 0.0
    for kind, patterns in args["kernels"].items():
        s, n = op_seconds(red, patterns, args.get("programs"))
        flops += n * per_call[kind]
        seconds += s
    if not seconds:
        return None
    return 100.0 * flops / cell["peaks"]["flops_per_s"] / seconds
