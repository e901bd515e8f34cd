"""device_idle_share (%): 1 - (union of the device's operation intervals
in the window / the window), averaged over chips. Layer: device. Moves:
tokens_per_s. Reads the ``XLA Ops`` line of each ``/device:TPU:n``
plane and the ``bench.window`` host span."""

LAYER = "device"
MOVES = "tokens_per_s"


def read(ctx):
    t = ctx["trace"]
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
