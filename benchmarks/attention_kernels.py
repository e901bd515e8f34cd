"""How a compiled train step lowered its attention, and the fused kernel's
share of the ``attention`` scope's device time.

    python3 chipbench/scoped.py --workload granite.train4k.dp1 --seed 7 \\
        --seconds 10 --keep chiprun_out/scoped
    python3 benchmarks/attention_kernels.py chiprun_out/scoped granite.train4k.dp1

Reads what ``scoped.py --keep`` writes: the step's HLO text
(``<cell>.scoped.hlo.txt.gz``) and, where it is there, the trace of the
window (``<cell>.scoped.xplane.pb``). From the HLO text, the attention
calls of the compiled program (a scanned layer stack compiles one body
for all its layers):

- ``kernel_calls``: the TPU kernel's ``tpu_custom_call`` instructions,
  by kernel name (``splash_mha_fwd_*``: a forward, ``splash_mha_dkv_*``:
  the fused backward);
- ``blockwise_calls``: the calls that lowered to the blockwise scan: its
  outer ``while`` loops (over q blocks), whose ``op_name`` holds one
  ``while`` after ``blockwise_attention`` (the forward, its
  recomputation and its transpose each have their own).

From the trace joined to the HLO (``chipbench/harness/scopes.py``): the
``attention`` scope's device time in the window, the part of it spent in
the kernel's instructions, and their ratio. Prints one JSON line.
"""

from __future__ import annotations

import gzip
import json
import os
import re
import sys
from collections import Counter

KERNEL = re.compile(r"^splash_mha_\w+")
BLOCKWISE = "blockwise_attention"
_CUSTOM_CALL = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*custom_call_target="tpu_custom_call"')
_WHILE = re.compile(r'^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=.*\swhile\(.*op_name="([^"]*)"')


def kernel_instructions(hlo_text):
    """Names of the attention kernel's custom-call instructions."""
    names = (m.group(1) for m in map(_CUSTOM_CALL.match, hlo_text.splitlines()) if m)
    return [n for n in names if KERNEL.match(n)]


def count(hlo_text):
    """{"kernel_calls", "kernel_by_name", "blockwise_calls"} of one
    compiled module's text (see the module docstring)."""
    kernels = kernel_instructions(hlo_text)
    outer = 0
    for m in filter(None, map(_WHILE.match, hlo_text.splitlines())):
        parts = m.group(1).split("/")
        hit = next((i for i, c in enumerate(parts) if BLOCKWISE in c), None)
        outer += hit is not None and parts[hit + 1:].count("while") == 1
    return {"kernel_calls": len(kernels),
            "kernel_by_name": dict(Counter(KERNEL.match(n).group(0) for n in kernels)),
            "blockwise_calls": outer}


def kernel_share(xplane_path, hlo_text):
    """(attention scope seconds, kernel seconds) in the trace's window,
    per device, and the kernel's share of the scope in %."""
    from chipbench.harness import scopes, trace
    ops, spans = trace.load(xplane_path)
    joined = scopes.join(ops, scopes.load_modules(xplane_path), spans, hlo_text, len(ops))
    lo, hi = next((s, e) for s, e, n in spans if n == trace.WINDOW_SPAN)
    kernels = set(kernel_instructions(hlo_text))
    kernel_ns = sum(min(e, hi) - max(s, lo) for evs in ops.values()
                    for s, e, n, _ in evs if n in kernels and e > lo and s < hi)
    attention_s = joined["scope_s"].get("attention", 0.0)
    kernel_s = kernel_ns * 1e-9 / len(ops)
    return attention_s, kernel_s, 100 * kernel_s / attention_s if attention_s else None


def main(argv=None):
    argv = sys.argv[1:] if argv is None else argv
    if len(argv) != 2:
        print(__doc__.split("\n\n")[1], file=sys.stderr)
        return 2
    keep, cell = argv
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    if root not in sys.path:
        sys.path.insert(0, root)
    stem = os.path.join(keep, cell)
    with gzip.open(stem + ".scoped.hlo.txt.gz", "rt") as f:
        text = f.read()
    out = {"workload": cell, **count(text)}
    if os.path.exists(stem + ".scoped.xplane.pb"):
        attention_s, kernel_s, share = kernel_share(stem + ".scoped.xplane.pb", text)
        out.update(attention_s=attention_s, kernel_s=kernel_s,
                   kernel_share_of_attention=share)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
