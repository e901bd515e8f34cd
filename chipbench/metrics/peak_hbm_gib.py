"""peak_hbm_gib (GiB): ``memory_stats()["peak_bytes_in_use"]`` after the
window, the largest over the chips used. Layer: train step. Moves:
tokens_per_s (a limit to fit under, so it bounds batch and depth)."""

LAYER = "train step"
MOVES = "tokens_per_s"


def read(ctx):
    return ctx["peak_bytes"] / 2**30 if ctx["peak_bytes"] else None
