"""The join of a chip trace with the step's compiled HLO: by hand on a
made-up module and trace, and on a scoped trace recorded on a TPU v5e."""

import gzip
import os

import pytest

from chipbench.harness import scopes, spec, trace

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
CFG = scopes.config()

HLO = """HloModule jit_step_fn, is_scheduled=true

FileNames
1 "model.py"

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8]{0} parameter(0)
  %multiply.3 = f32[8]{0} multiply(f32[8]{0} %param_0, f32[8]{0} %param_0), metadata={op_name="jit(step_fn)/transpose(jvp(attention))/mul"}
  ROOT %bitcast.4 = f32[8]{0} bitcast(f32[8]{0} %multiply.3)
}

ENTRY %main.11 (Arg_0.1: f32[8]) -> f32[8] {
  %Arg_0.1 = f32[8]{0} parameter(0)
  %fusion.1 = f32[8]{0} fusion(f32[8]{0} %Arg_0.1), kind=kLoop, calls=%fused_computation.1
  %dot.2 = f32[8]{0} dot(f32[8]{0} %fusion.1, f32[8]{0} %Arg_0.1), metadata={op_name="jit(step_fn)/while/body/jvp()/attention/dot_general"}
  %add.5 = f32[8]{0} add(f32[8]{0} %dot.2, f32[8]{0} %dot.2), metadata={op_name="jit(step_fn)/transpose(jvp())/checkpoint/rematted_computation/mlp/add"}
  %mul.6 = f32[8]{0} multiply(f32[8]{0} %add.5, f32[8]{0} %add.5), metadata={op_name="mul;jit(step_fn)/optimizer/jit(clip)/mul"}
  %all-reduce.7 = f32[8]{0} all-reduce(f32[8]{0} %mul.6), to_apply=%add, metadata={op_name="jit(step_fn)/aggregate/reduce/psum"}
  %copy.8 = f32[8]{0} copy(f32[8]{0} %all-reduce.7)
  %sub.9 = f32[8]{0} subtract(f32[8]{0} %copy.8, f32[8]{0} %copy.8), metadata={op_name="jit(step_fn)/jvp(embed)/sub"}
  %custom-call.10 = f32[8]{0} custom-call(f32[8]{0} %sub.9), custom_call_target="tpu_custom_call", metadata={op_name="jit(step_fn)/aggregate/encode/pallas_call"}
  %copy.12 = f32[8]{0} copy(f32[8]{0} %Arg_0.1)
  ROOT %tuple.13 = (f32[8]{0}, f32[8]{0}) tuple(f32[8]{0} %custom-call.10, f32[8]{0} %copy.12)
}
"""


def test_op_names_and_classification_by_hand():
    names = scopes.op_names(HLO)
    assert scopes.module_name(HLO) == "jit_step_fn"
    # the fusion has no metadata: its fused root (a bitcast) has none
    # either, so it takes the multiply's
    assert names["fusion.1"] == "jit(step_fn)/transpose(jvp(attention))/mul"
    # an instruction without metadata takes its first user's: copy.8
    # feeds sub.9; copy.12 feeds only the root tuple, which has none
    assert names["copy.8"] == "jit(step_fn)/jvp(embed)/sub"
    assert names["copy.12"] is None and names["tuple.13"] is None
    want = {
        "fusion.1": ("backward", "attention"),
        "dot.2": ("forward", "attention"),
        "add.5": ("recompute", "mlp"),
        "mul.6": ("optimizer", "optimizer"),          # second part of a merged name
        "all-reduce.7": ("aggregate", "reduce"),
        "copy.8": ("forward", "embed"),
        "copy.12": ("unscoped", None),
        "sub.9": ("forward", "embed"),
        "custom-call.10": ("aggregate", "encode"),
    }
    assert {n: scopes.classify(names[n], CFG) for n in want} == want
    assert scopes.classify("jit(step_fn)/while/body/attention/cos", CFG) == ("unscoped", "attention")
    assert scopes.classify("jit(step_fn)/aggregate/pack/reduce_max", CFG) == ("aggregate", "pack")
    assert scopes.classify(None, CFG) == ("unscoped", None)
    assert "FileNames" not in scopes.without_tables(HLO)
    assert scopes.op_names(scopes.without_tables(HLO)) == names


def _made_up():
    ops = {0: [(0, 10, "dot.2", ""), (12, 20, "fusion.1", ""), (20, 30, "add.5", ""),
               (35, 40, "mul.6", ""), (52, 60, "all-reduce.7", ""), (60, 70, "copy.12", ""),
               (70, 80, "custom-call.10", ""), (80, 90, "sub.9", "")],
           1: [(0, 30, "dot.2", ""), (30, 34, "missing.1", "")]}
    modules = {0: [(0, 40, "jit_step_fn(123)"), (50, 95, "jit_step_fn(123)"),
                   (96, 99, "jit_other(5)")]}
    spans = [(0, 100, "bench.window"), (40, 52, "bench.readback")]
    return ops, modules, spans


def test_join_by_phase_and_scope_by_hand():
    ops, modules, spans = _made_up()
    j = scopes.join(ops, modules, spans, HLO, 2)
    ns = lambda d: {k: pytest.approx(v * 1e-9) for k, v in d.items()}
    # averaged over the two devices; device 1's unknown op is unscoped
    assert j["phase_s"] == ns({"forward": 25, "backward": 4, "recompute": 5,
                               "optimizer": 2.5, "aggregate": 9, "unscoped": 7})
    assert j["scope_s"] == ns({"attention": 24, "mlp": 5, "optimizer": 2.5,
                               "reduce": 4, "encode": 5, "embed": 5})
    assert sum(j["phase_s"].values()) == pytest.approx(j["busy_s"])
    assert j["phase_scope_s"] == ns({"forward/attention": 20, "forward/embed": 5,
                                     "backward/attention": 4, "recompute/mlp": 5,
                                     "optimizer/optimizer": 2.5, "aggregate/reduce": 4,
                                     "aggregate/encode": 5, "unscoped/-": 7})
    assert j["busy_s"] == pytest.approx(52.5e-9)
    assert j["joined_share"] == pytest.approx(45.5 / 52.5)
    assert j["scoped_share"] == pytest.approx(45.5 / 52.5)
    assert j["unmatched_s"] == pytest.approx(2e-9)
    assert j["unscoped_ops"] == [["copy.12", pytest.approx(5e-9)],
                                 ["missing.1", pytest.approx(2e-9)]]


def test_idle_splits_inside_and_between_steps_by_hand():
    ops, modules, spans = _made_up()
    j = scopes.join(ops, modules, spans, HLO, 2)
    # device 0 idle: [10,12] [30,35] [40,52] [90,100]; steps [0,40] [50,95]
    assert j["step_runs"] == 2                      # jit_other is not the step
    assert j["idle_s"] == pytest.approx(29e-9)
    assert j["idle_in_step_s"] == pytest.approx(14e-9)
    assert j["idle_between_s"] == pytest.approx(15e-9)
    # each in-step gap is named by the operation after it
    assert j["in_step_gaps"] == [["optimizer", pytest.approx(5e-9), "mul.6"],
                                 ["unscoped", pytest.approx(5e-9), "none"],
                                 ["attention", pytest.approx(2e-9), "fusion.1"],
                                 ["reduce", pytest.approx(2e-9), "all-reduce.7"]]
    assert j["idle_in_step_by_scope"]["unscoped"] == pytest.approx(5e-9)
    m = scopes.per_step(j, 2)
    assert set(m) == set(CFG["metrics"])
    assert m["idle_in_step_ms_per_step"] == pytest.approx(7e-6)
    assert m["forward_ms_per_step"] == pytest.approx(12.5e-6)
    assert m["attention_ms_per_step"] == pytest.approx(12e-6)
    # nothing matched: missing, never 0
    assert m["ssd_scan_ms_per_step"] is None and m["head_loss_ms_per_step"] is None


def test_join_without_step_modules_reads_no_idle_metric():
    ops, _, spans = _made_up()
    j = scopes.join(ops, {}, spans, HLO, 1)
    assert j["step_runs"] == 0 and j["idle_in_step_s"] == 0
    assert j["idle_between_s"] == pytest.approx(j["idle_s"])
    assert scopes.per_step(j, 2)["idle_in_step_ms_per_step"] is None
    lines = list(scopes.log_lines(j, 2))
    assert len(lines) == 5 and lines[0].startswith("phases")


SCOPED = sorted(p for p in os.listdir(DATA) if p.endswith(".scoped.xplane.pb"))


@pytest.mark.parametrize("name", SCOPED)
def test_recorded_scoped_chip_trace(name):
    path = os.path.join(DATA, name)
    with gzip.open(path.replace(".xplane.pb", ".hlo.txt.gz"), "rt") as f:
        hlo = f.read()
    ops, spans = trace.load(path)
    red = trace.reduce(ops, spans, spec.names(), len(ops))
    j = scopes.join(ops, scopes.load_modules(path), spans, hlo, len(ops))
    assert j["busy_s"] == pytest.approx(red["busy_s"])
    assert j["joined_share"] >= 0.9
    assert j["unmatched_s"] <= 0.01 * j["busy_s"]
    assert sum(j["phase_s"].values()) == pytest.approx(j["busy_s"], rel=0.01)
    assert j["step_runs"] > 0
    window_idle = red["window_s"] - red["busy_s"]
    assert j["idle_in_step_s"] + j["idle_between_s"] == pytest.approx(window_idle, rel=0.01)
    m = scopes.per_step(j, j["step_runs"])
    for k in ("forward", "backward", "recompute", "optimizer", "attention",
              "head_loss", "idle_in_step"):
        assert m[f"{k}_ms_per_step"] is not None, k
