"""The KDA kernels against the roofline: the least time the chip could
take for every executed call (the larger of the recurrence's FLOPs over
peak and the call's bytes over peak, ``costs_kimi_linear.kda_core_cost``)
over the calls' device time.  ``args.kernels`` maps a kind of call to the
op-name patterns that find it; a kind the trace does not hold adds
nothing, and with no call at all the metric is left out."""

from .. import costs, costs_kimi_linear, weights_kimi_linear as weights
from ..trace import op_seconds


def reduce(red, counters, cell):
    args, tr = cell["spec"]["args"], cell["traffic"]
    d = weights.dims_of(cell["config"])
    per_call = {"fwd": costs_kimi_linear.kda_core_cost(
        d, tr["rows_per_chip"], tr["sequence_length"])}
    least = seconds = 0.0
    for kind, patterns in args["kernels"].items():
        s, n = op_seconds(red, patterns)
        least += n * costs.least_seconds(*per_call[kind], cell["peaks"])
        seconds += s
    if not seconds:
        return None
    return 100.0 * least / seconds
