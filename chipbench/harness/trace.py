"""From a profiler trace to the numbers the per-layer metrics read.

A device plane is ``/device:TPU:<n>``; its operations are the events of
its ``XLA Ops`` line. An event's name is its HLO instruction
(``%fusion.12 = f32[...] fusion(...)``); the op name is the part before
`` = ``. Loops and conditionals appear as events that hold their
bodies' events, so only the innermost events (those that hold no other)
count as operations. The host spans are the benchmark's own
``TraceAnnotation``s (names starting ``bench.``); ``bench.window``
bounds the measured window. Everything is in nanoseconds on the
profiler's common clock.

Per device: busy time is the union of its operation intervals inside
the window; collective time is the summed duration of operations whose
name matches ``names.json``'s ``collectives`` pattern, and its exposed
part the union of those intervals less the union of all other
operations; a kernel's time is the summed duration of operations whose
whole instruction text matches its pattern. Idle gaps are the stretches
of the window with no operation on device 0, each named by the host
span that overlaps it most (``none`` where no span does).
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
TOP = 10


def union(intervals):
    """Sorted, merged (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def length(intervals):
    return sum(e - s for s, e in intervals)


def subtract(a, b):
    """Union ``a`` less union ``b`` (both merged and sorted)."""
    out, j = [], 0
    for s, e in a:
        cur = s
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < e:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < e:
            out.append((cur, e))
    return out


def gaps(busy, lo, hi):
    return subtract([(lo, hi)], busy)


def op_name(text):
    """``%fusion.12 = ...`` -> ``fusion.12``."""
    head = text.split(" = ", 1)[0]
    return head[1:] if head.startswith("%") else head


def op_label(name, text):
    """``fusion.12 bf16[4096,8192]``: the op and the type it writes."""
    rest = text.split(" = ", 1)[1] if " = " in text else ""
    out = re.sub(r"\{[^}]*\}", "", rest.split(" ", 1)[0]) if rest and rest[0] != "(" else ""
    return f"{name} {out}".strip()[:120]


def innermost(events):
    """The events of one line that hold no other event."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    keep = []
    for i, e in enumerate(events):
        nxt = events[i + 1] if i + 1 < len(events) else None
        if nxt is None or nxt[0] >= e[1]:
            keep.append(e)
    return keep


def load(path):
    """(ops per device {index: [(start, end, op name, instruction)]},
    host spans [(start, end, name)]) from one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    evs.append((e.start_ns, e.start_ns + e.duration_ns,
                                op_name(e.name), e.name))
            ops[int(m.group(1))] = innermost(evs)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith("bench."):
                        spans.append((e.start_ns, e.start_ns + e.duration_ns, e.name))
    return ops, spans


def reduce(ops, spans, names, n_devices):
    """The numbers per-layer metrics read (see the module docstring)."""
    win = [(s, e) for s, e, n in spans if n == WINDOW_SPAN]
    if not win or not ops:
        raise ValueError("trace holds no window span or no device operations")
    lo, hi = win[0]
    coll = re.compile(names["collectives"])
    kernels = {k: re.compile(p) for k, p in names["kernels"].items()}
    devs = sorted(ops)[:n_devices]
    busy_ns, coll_ns, exposed_ns = [], [], []
    kernel_ns = defaultdict(float)
    kernel_hits = defaultdict(int)
    op_time = defaultdict(float)
    first, last = [], []
    for d in devs:
        evs = [(max(s, lo), min(e, hi), n, ln) for s, e, n, ln in ops[d] if e > lo and s < hi]
        busy = union([(s, e) for s, e, _, _ in evs])
        busy_ns.append(length(busy))
        if busy:
            first.append(busy[0][0])
            last.append(busy[-1][1])
        is_coll = [bool(coll.search(n)) for _, _, n, _ in evs]
        c_iv = union([(s, e) for (s, e, _, _), c in zip(evs, is_coll) if c])
        other = union([(s, e) for (s, e, _, _), c in zip(evs, is_coll) if not c])
        coll_ns.append(sum(e - s for (s, e, _, _), c in zip(evs, is_coll) if c))
        exposed_ns.append(length(subtract(c_iv, other)))
        for s, e, n, ln in evs:
            op_time[op_label(n, ln)] += (e - s) / len(devs)
            for k, pat in kernels.items():
                if pat.search(ln):
                    kernel_ns[k] += (e - s) / len(devs)
                    kernel_hits[k] += 1
    busy0 = union([(max(s, lo), min(e, hi)) for s, e, _, _ in ops[devs[0]] if e > lo and s < hi])
    idle = []
    for gs, ge in gaps(busy0, lo, hi):
        best, best_ov = "none", 0
        for s, e, n in spans:
            ov = min(e, ge) - max(s, gs)
            if n != WINDOW_SPAN and ov > best_ov:
                best, best_ov = n, ov
        idle.append((best, (ge - gs) * 1e-9))
    idle.sort(key=lambda x: -x[1])
    mean = lambda xs: sum(xs) / len(xs)
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": mean(busy_ns) * 1e-9,
        "device_span_s": (mean(last) - mean(first)) * 1e-9 if first else 0.0,
        "collective_s": mean(coll_ns) * 1e-9,
        "exposed_collective_s": mean(exposed_ns) * 1e-9,
        "kernel_s": {k: kernel_ns[k] * 1e-9 for k in kernels if kernel_hits[k]},
        "breakdown": {
            "device_ops": [[n, t * 1e-9] for n, t in
                           sorted(op_time.items(), key=lambda x: -x[1])[:TOP]],
            "idle_gaps": [[n, t] for n, t in idle[:TOP]],
        },
    }


def reduce_dir(trace_dir, names, n_devices):
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True)
    if not paths:
        raise ValueError(f"no .xplane.pb under {trace_dir}")
    ops, spans = load(max(paths, key=os.path.getmtime))
    return reduce(ops, spans, names, n_devices)
