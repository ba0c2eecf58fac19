"""Required forward and backward FLOPs of a Kimi-Linear step (from shapes
and from the pairs the steps of the window reported as held, nothing
recomputed) times steps per second, over the chip's peak."""

from .. import costs_kimi_linear as costs, weights_kimi_linear as weights


def reduce(red, counters, cell):
    rate = counters.get("train_tokens_per_s_per_chip")
    pairs = counters.get("pairs_held_per_step")
    if not rate or pairs is None:
        return None
    tr = cell["traffic"]
    rows, seq = tr["rows_per_chip"], tr["sequence_length"]
    per_step = costs.train_flops_per_step(
        weights.dims_of(cell["config"]), rows, seq, pairs)
    return 100.0 * per_step * rate / (rows * seq) / \
        cell["peaks"]["flops_per_s"]
