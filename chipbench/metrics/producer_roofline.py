"""producer_roofline (%): the least time for the producer's required
bytes (``harness/counts.py``: stream in; sketch, bitmap
and max-magnitudes out) at the HBM rate of
``peaks.json``, over the kernel's device time per step. Only the bytes
bound is computed: the table has no sourced vector-unit rate to price
the kernel's elementwise work against (``harness/counts.py``). The
kernel is the operations matching ``names.json``'s ``producer``
pattern; None when none matches. Layer: codec kernels. Moves:
tokens_per_s."""

from chipbench.harness import counts

LAYER = "codec kernels"
MOVES = "tokens_per_s"
KERNEL = "producer"


def read(ctx):
    t = ctx["trace"]["kernel_s"].get(KERNEL)
    if not t:
        return None
    nbytes = getattr(counts, KERNEL)(ctx["param_count"], ctx["config"]["train"]["compression"])
    return counts.roofline_share(nbytes, t / ctx["steps"], ctx["peaks"])
