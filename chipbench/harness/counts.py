"""Bytes the codec kernels must move, from shapes.

Counted from the wire's definition, so they are the same for any
implementation: the producer reads the gradient stream (float32) and
writes the sketch (``rows * lanes`` float32 cells a block), the bitmap
(one bit an element) and one float32 max-magnitude a block; the
consumer reads the sketch and the bitmap and writes the values
(float32) and the unpeeled mask (one byte an element). ``N`` is the
number of gradient elements, rounded up to whole blocks.

Only the bytes bound is computed. The kernels' work is elementwise and
integer arithmetic on the vector unit, and ``peaks.json`` has no sourced
rate for it; pricing it at the matrix unit's FLOP/s would make the
operations look free. Until a sourced vector-unit rate is in the table,
a roofline share here is the least time for the bytes at the HBM rate
over the measured time: a bound from below on the share, never above
100%.
"""

from __future__ import annotations


def _blocks(n, c):
    group = max(1, round(c["rows"] / c["ratio"]))
    block = group * c["lanes"]
    nb = -(-n // block)
    return nb, nb * block


def producer(n, c):
    nb, elems = _blocks(n, c)
    sketch = nb * c["rows"] * c["lanes"]
    return 4 * elems + 4 * sketch + elems / 8 + 4 * nb


def consumer(n, c):
    nb, elems = _blocks(n, c)
    sketch = nb * c["rows"] * c["lanes"]
    return 4 * sketch + elems / 8 + 4 * elems + elems


def roofline_share(nbytes, kernel_s_per_step, peaks):
    """The least time for ``nbytes`` at the HBM rate over the measured
    time, in %."""
    return 100.0 * nbytes / peaks["hbm_bytes_per_s"] / kernel_s_per_step
