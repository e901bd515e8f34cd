"""exposed_collective_ms_per_step (ms): the part of the collective
operations' intervals (see collective_ms_per_step) in which no other
operation runs on that device, per step, averaged over chips. Layer:
aggregation. Moves: tokens_per_s. None when no collective matches."""

LAYER = "aggregation"
MOVES = "tokens_per_s"


def read(ctx):
    if ctx["trace"]["collective_s"] <= 0:
        return None
    return 1e3 * ctx["trace"]["exposed_collective_s"] / ctx["steps"]
