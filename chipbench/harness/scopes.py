"""From a profiler trace and the step's compiled HLO to time by program scope.

The program names its work with ``jax.named_scope`` (``scopes.json``'s
``scopes``: the model's layers, the codec's stages, the optimizer). The
names live in each HLO instruction's ``op_name`` metadata, which the
chip trace does not carry, but the trace's ``XLA Ops`` events are named
by the instructions of the compiled module, so the module's HLO text
(``Compiled.as_text()``) joins the two. A fusion without metadata takes
the ``op_name`` of the instruction nearest its fused computation's root
that has one; any other instruction without metadata (a copy, an async
start or done that XLA added) takes that of its first user.

Each operation gets a phase and a scope from its ``op_name``. The phase
is the first of ``phases`` whose mark the name holds (JAX's name stack
writes the forward under ``jvp(``, the backward under ``transpose(``,
the recomputation of a ``jax.checkpoint`` under ``rematted_computation``),
else the first of ``phase_scopes`` on its stack, else ``unscoped``. The
scope is the innermost program scope on its stack, or none. An
operation missing from the HLO is ``unscoped`` with no scope.

Device 0's idle time in the window splits by the trace's ``XLA Modules``
line: idle inside an execution of the step's module, and idle between
executions. Each stretch of idle inside a step is named by the operation
that follows it: its scope, else its phase. Times are per device and
averaged over devices, in seconds; per-step numbers are in ms.
"""

from __future__ import annotations

import bisect
import json
import os
import re
from collections import defaultdict

from chipbench.harness import spec, trace

MODULES_LINE = "XLA Modules"
TABLES = ("FileNames", "FunctionNames", "FileLocations", "StackFrames")

_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_INSTRUCTION = re.compile(r"^\s+(?:ROOT\s+)?%?([\w.\-]+)\s*=\s")
_OP_NAME = re.compile(r'op_name="((?:[^"\\]|\\.)*)"')
_CALLS = re.compile(r"\bcalls=%?([\w.\-]+)")
_CALLED = re.compile(r"\b(?:calls|to_apply|body|condition|branch_computations|"
                     r"called_computations)=(?:\{[^}]*\}|%?[\w.\-]+)")
_OPERAND = re.compile(r"%([\w.\-]+)")
_WRAPPED = re.compile(r"[\w.\-]+\((.*)\)")


def config():
    with open(os.path.join(spec.BENCH_DIR, "scopes.json")) as f:
        return json.load(f)


def module_name(hlo_text):
    """``HloModule jit_step_fn, ...`` -> ``jit_step_fn``."""
    m = re.match(r"HloModule\s+([\w.\-]+)", hlo_text)
    return m.group(1) if m else None


def without_tables(hlo_text):
    """The HLO text less its source-location tables (file paths and
    lines), which the join does not read."""
    blocks = hlo_text.split("\n\n")
    return "\n\n".join(b for b in blocks if b.split("\n", 1)[0] not in TABLES)


def op_names(hlo_text):
    """{instruction name: op_name} over every computation of the module
    (None where neither the instruction, its fusion nor its users have
    one)."""
    comps, own, calls, users, cur = {}, {}, {}, defaultdict(list), None
    for line in hlo_text.splitlines():
        m = _INSTRUCTION.match(line)
        if m and cur is not None:
            name = m.group(1)
            comps[cur].append(name)
            on = _OP_NAME.search(line)
            own[name] = on.group(1).replace("\\'", "'").replace('\\"', '"') if on else None
            c = _CALLS.search(line)
            if c:
                calls[name] = c.group(1)
            body = _OP_NAME.sub("", _CALLED.sub("", line[m.end():]))
            for operand in dict.fromkeys(_OPERAND.findall(body)):
                users[operand].append(name)
            continue
        m = _COMPUTATION.match(line)
        if m:
            cur = m.group(1)
            comps[cur] = []

    resolved = {}

    def first(names, depth):
        return next((r for r in (resolve(n, depth + 1) for n in names) if r), None)

    def resolve(name, depth=0):
        if name not in resolved:
            resolved[name] = None                # a cycle reads None
            got = own.get(name)
            if got is None and depth < 8:
                if name in calls:
                    got = first(reversed(comps.get(calls[name], [])), depth)
                got = got or first(users.get(name, []), depth)
            resolved[name] = got
        return resolved[name]

    return {n: resolve(n) for n in own}


def _unwrap(component):
    """``transpose(jvp(attention))`` -> ``attention``."""
    while m := _WRAPPED.fullmatch(component):
        component = m.group(1)
    return component


def classify(op_name, cfg):
    """(phase, innermost program scope or None) of one ``op_name``. A
    name XLA merged from several (``a;b``) takes its first part that
    gives a phase or a scope."""
    vocab = set(cfg["scopes"])
    for part in (op_name or "").split(";"):
        stack = [_unwrap(c) for c in part.split("/")]
        scope = next((c for c in reversed(stack) if c in vocab), None)
        phase = next((p for p, mark in cfg["phases"] if mark in part), None)
        phase = phase or next((s for s in cfg["phase_scopes"] if s in stack), None)
        if phase or scope:
            return phase or "unscoped", scope
    return "unscoped", None


def load_modules(path):
    """{device index: [(start, end, module execution name)]} from the
    ``XLA Modules`` line of each device plane of one ``.xplane.pb``."""
    from jax.profiler import ProfileData
    out = {}
    for plane in ProfileData.from_file(path).planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        if m:
            out[int(m.group(1))] = [
                (e.start_ns, e.start_ns + e.duration_ns, e.name)
                for line in plane.lines if line.name == MODULES_LINE
                for e in line.events]
    return out


def _intersect(a, b):
    return trace.subtract(a, trace.subtract(a, b))


def join(ops, modules, spans, hlo_text, n_devices, cfg=None):
    """The window's device time by phase and scope, and device 0's idle
    time split by step executions (see the module docstring). ``ops``
    and ``spans`` as ``trace.load`` returns them, ``modules`` as
    ``load_modules`` does."""
    cfg = cfg or config()
    win = [(s, e) for s, e, n in spans if n == trace.WINDOW_SPAN]
    if not win or not ops:
        raise ValueError("trace holds no window span or no device operations")
    lo, hi = win[0]
    names = op_names(hlo_text)
    devs = sorted(ops)[:n_devices]
    phase_ns, scope_ns, both_ns = defaultdict(float), defaultdict(float), defaultdict(float)
    unscoped_ns, busy_ns = defaultdict(float), []
    joined_ns = unmatched_ns = 0.0
    labels = {}
    for d in devs:
        evs = [(max(s, lo), min(e, hi), n, ln) for s, e, n, ln in ops[d] if e > lo and s < hi]
        busy_ns.append(trace.length(trace.union([(s, e) for s, e, _, _ in evs])))
        for s, e, n, ln in evs:
            if n not in labels:
                labels[n] = classify(names.get(n), cfg)
            phase, scope = labels[n]
            t = (e - s) / len(devs)
            phase_ns[phase] += t
            both_ns[f"{phase}/{scope or '-'}"] += t
            if scope:
                scope_ns[scope] += t
            if phase != "unscoped" or scope:
                joined_ns += t
            else:
                unscoped_ns[trace.op_label(n, ln)] += t
                unmatched_ns += t if n not in names else 0.0

    d0 = devs[0]
    evs0 = sorted((max(s, lo), min(e, hi), n, ln) for s, e, n, ln in ops[d0] if e > lo and s < hi)
    idle = trace.gaps(trace.union([(s, e) for s, e, _, _ in evs0]), lo, hi)
    step = module_name(hlo_text)
    runs = [(max(s, lo), min(e, hi)) for s, e, n in modules.get(d0, [])
            if n.split("(", 1)[0] == step and e > lo and s < hi]
    in_step = _intersect(idle, trace.union(runs))
    starts = [s for s, _, _, _ in evs0]
    gaps, gap_ns = [], defaultdict(float)
    for gs, ge in in_step:
        i = bisect.bisect_left(starts, ge)
        nxt = evs0[i] if i < len(evs0) else None
        phase, scope = labels[nxt[2]] if nxt else ("unscoped", None)
        gaps.append((scope or phase, (ge - gs) * 1e-9,
                     trace.op_label(nxt[2], nxt[3]) if nxt else "none"))
        gap_ns[scope or phase] += ge - gs
    gaps.sort(key=lambda g: -g[1])
    busy = sum(busy_ns) / len(busy_ns)
    s = lambda d: {k: v * 1e-9 for k, v in sorted(d.items(), key=lambda x: -x[1])}
    return {
        "busy_s": busy * 1e-9,
        "phase_s": s(phase_ns),
        "scope_s": s(scope_ns),
        "phase_scope_s": s(both_ns),
        "joined_share": joined_ns / busy if busy else 0.0,
        "scoped_share": sum(scope_ns.values()) / busy if busy else 0.0,
        "unmatched_s": unmatched_ns * 1e-9,
        "unscoped_ops": [[n, t] for n, t in list(s(unscoped_ns).items())[:trace.TOP]],
        "step_runs": len(runs),
        "idle_s": trace.length(idle) * 1e-9,
        "idle_in_step_s": trace.length(in_step) * 1e-9,
        "idle_between_s": (trace.length(idle) - trace.length(in_step)) * 1e-9,
        "idle_in_step_by_scope": s(gap_ns),
        "in_step_gaps": [list(g) for g in gaps[:trace.TOP]],
    }


def per_step(joined, steps, cfg=None):
    """``scopes.json``'s metrics, in ms per step: None where nothing in
    the trace matched, so a lost scope shows as a missing number."""
    cfg = cfg or config()
    out = {}
    for name, src in cfg["metrics"].items():
        if "idle" in src:
            t, found = joined["idle_in_step_s"], joined["step_runs"] > 0
        else:
            key = "phase" if "phase" in src else "scope"
            t = joined[key + "_s"].get(src[key], 0.0)
            found = t > 0
        out[name] = 1e3 * t / steps if found else None
    return out


def log_lines(joined, steps):
    """What a traced run logs of the join, one line per item."""
    ms = lambda t: f"{1e3 * t / steps:.3f}"
    yield "phases (ms/step): " + ", ".join(f"{k} {ms(v)}" for k, v in joined["phase_s"].items())
    yield "scopes (ms/step): " + ", ".join(f"{k} {ms(v)}" for k, v in joined["scope_s"].items())
    yield (f"busy time joined to a scope or phase {100 * joined['joined_share']:.2f}%, "
           f"carrying a program scope {100 * joined['scoped_share']:.2f}%")
    yield ("longest in-step gaps (scope, before op): "
           + ", ".join(f"{n} {1e6 * t:.1f} us before {op}" for n, t, op in joined["in_step_gaps"]))
    yield (f"idle between steps {joined['idle_between_s']:.6f} s "
           f"({ms(joined['idle_between_s'])} ms/step), inside steps "
           f"{joined['idle_in_step_s']:.6f} s over {joined['step_runs']} step runs")
