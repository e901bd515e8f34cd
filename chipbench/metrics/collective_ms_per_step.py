"""collective_ms_per_step (ms): summed device durations of the
operations whose name matches ``names.json``'s ``collectives`` pattern
(all-reduce, reduce-scatter, all-gather, collective-permute,
all-to-all, with their -start/-done halves), per step, averaged over
chips. Layer: aggregation. Moves: tokens_per_s. None when no operation
matches, so a rename shows as a missing metric."""

LAYER = "aggregation"
MOVES = "tokens_per_s"


def read(ctx):
    t = ctx["trace"]["collective_s"]
    return 1e3 * t / ctx["steps"] if t > 0 else None
