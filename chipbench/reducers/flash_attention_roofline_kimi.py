"""The three flash kernels of the MLA layer against the MXU roofline:
causal FLOPs of every executed call at keys wider than values
(``costs_kimi_linear.flash_flops``; ``args.kernels`` maps fwd, dq, dkv to
the op-name patterns that find them) over the peak, over their device
time.  With no call in the trace the metric is left out."""

from .. import costs_kimi_linear, weights_kimi_linear as weights
from ..trace import op_seconds


def reduce(red, counters, cell):
    args, tr = cell["spec"]["args"], cell["traffic"]
    per_call = costs_kimi_linear.flash_flops(
        weights.dims_of(cell["config"]), tr["rows_per_chip"],
        tr["sequence_length"])
    flops = seconds = 0.0
    for kind, patterns in args["kernels"].items():
        s, n = op_seconds(red, patterns)
        flops += n * per_call[kind]
        seconds += s
    if not seconds:
        return None
    return 100.0 * flops / cell["peaks"]["flops_per_s"] / seconds
