"""Required forward and backward FLOPs per token (nothing recomputed)
times the run's tokens per second per chip, over the chip's peak."""

from .. import costs, reference


def reduce(red, counters, cell):
    rate = counters.get("train_tokens_per_s_per_chip")
    if not rate:
        return None
    d = reference.dims_of(cell["config"])
    per_token = costs.train_flops_per_token(
        d, cell["traffic"]["sequence_length"])
    return 100.0 * per_token * rate / cell["peaks"]["flops_per_s"]
