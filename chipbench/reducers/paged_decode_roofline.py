"""The paged-decode kernel against the HBM roofline: bytes of the K and V
pages the traced ticks' streams hold (pages up to each stream's length),
over the peak bandwidth, over the kernel's device time (args: ops,
programs)."""

from .. import costs, reference
from ..trace import op_seconds
from ._steps import traced_steps


def reduce(red, counters, cell):
    args = cell["spec"]["args"]
    ticks = [s["decode"] for s in traced_steps(red, counters) if s["decode"]]
    seconds, calls = op_seconds(red, args["ops"], args.get("programs"))
    if not ticks or not calls:
        return None
    d = reference.dims_of(cell["config"])
    need = sum(d["n_layers"] * costs.paged_decode_bytes(
        d, t, counters["block_size"]) for t in ticks)
    return 100.0 * need / cell["peaks"]["hbm_bytes_per_s"] / seconds
