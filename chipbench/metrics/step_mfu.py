"""step_mfu (%): model FLOPs of the steps in the traced window over the
device's time for them (first operation to last, averaged over chips),
times chips and the peak FLOP/s of ``peaks.json``. Layer: train step.
Moves: tokens_per_s. FLOPs per token: the family's ``flops_per_token``
(6 x matmul parameters plus attention or the state space, causal
convention; no recomputation, no codec work)."""

LAYER = "train step"
MOVES = "tokens_per_s"


def read(ctx):
    t = ctx["trace"]["device_span_s"]
    if t <= 0:
        return None
    return 100.0 * ctx["flops_per_step"] * ctx["steps"] / (
        t * ctx["chips"] * ctx["peaks"]["flops_per_s"])
