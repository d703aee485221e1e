"""The scope and span reductions (``scopes.py``): whole-token scope
matching, the scope of fusions without metadata, per-study span sums,
the chip's unnamed idle time and the lead of device work over its host
span on the recorded trace, and the study cell's span metrics in a
traced run."""
import time

import pytest

from benchmarks.chip import run, scopes, trace
from benchmarks.chip.tests import test_bench_checks as checks
from benchmarks.chip.tests.test_bench_trace import SMALL


@pytest.mark.parametrize("op_name, scope", [
    ("jit(train_step)/jvp(embed)/jit(_take)/lt", "embed"),
    ("jit(train_step)/transpose(jvp(mixer))/dot_general", "mixer"),
    ("checkpoint/rematted_computation/ffn/mul", "ffn"),
    ("jit(step)/while/body/closed_call/head/reduce_sum", "head"),
    ("jit(train_step)/optimizer/sqrt", "optimizer"),
    ("jit(train_step)/transpose(jvp())/while/body/mixers/add", None),
    ("jit(train_step)/ffn_proj/add", None),
    ("reduce_sum", None),
])
def test_scope_is_a_whole_path_token(op_name, scope):
    assert scopes.scope_of(op_name) == scope


HLO = """\
HloModule m

%fused_computation.2 (param_0: f32[4]) -> f32[4] {
  %param_0 = f32[4]{0} parameter(0)
  %a = f32[4]{0} add(%param_0, %param_0), metadata={op_name="jit(f)/transpose(jvp(ffn))/add"}
  ROOT %m = f32[4]{0} multiply(%a, %a), metadata={op_name="jit(f)/jvp(ffn)/mul"}
}

%fused_computation.3 (param_0: f32[4]) -> f32[4] {
  ROOT %param_0.1 = f32[4]{0} parameter(0)
}

ENTRY %main.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  %convert_bitcast_fusion.2 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.2
  %fusion.3 = f32[4]{0} fusion(%p), kind=kLoop, calls=%fused_computation.3
  %dot.1 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(f)/optimizer/mul"}
  %copy.4 = f32[4]{0} copy(%fusion.3), metadata={op_name="jit(f)/while"}
  ROOT %convert.5 = f32[4]{0:T(8,128)} convert(%copy.4, %dot.1)
}
"""


def test_scope_map_and_split():
    m = scopes.scope_map(HLO)
    assert m["convert_bitcast_fusion.2"] == "ffn"   # by its instructions
    assert m["fusion.3"] is None and m["copy.4"] is None
    assert m["dot.1"] == "optimizer"
    assert m["convert.5"] == "optimizer"   # inserted: by its operands
    got = scopes.split({"convert_bitcast_fusion.2": 2.0, "dot.1": 1.0,
                        "copy.4": 0.5, "while.7": 0.25}, m)
    assert got["ffn"] == 2.0 and got["optimizer"] == 1.0
    assert got["unscoped"] == 0.5 and got["unknown"] == 0.25
    assert got["mixer"] == 0.0


def test_study_spans_sum_the_last_studies():
    spans = [("papas.study.plan", 0, 1), ("papas.gang.lower", 1, 2),
             ("papas.study.schedule", 1, 10),
             # studies 1 and 2: a lower, a compile, a run in each
             ("papas.study.plan", 20, 21), ("papas.study.schedule", 21, 28),
             ("papas.gang.build", 21, 21.5), ("papas.gang.lower", 21.5, 23),
             ("papas.gang.compile", 23, 26), ("papas.gang.run", 26, 27),
             ("papas.study.record", 28, 30),
             ("papas.study.plan", 40, 41), ("papas.study.schedule", 41, 46),
             ("papas.gang.build", 41, 41.5),
             ("papas.gang.lower", 41.5, 42), ("papas.gang.compile", 42, 44),
             ("papas.gang.run", 44, 45), ("papas.study.record", 46, 48)]
    got = scopes.study_spans(spans, 2)
    assert got["lower_s"] == pytest.approx((2.0 + 1.0) / 2)
    assert got["load_s"] == pytest.approx((3 + 2) / 2)
    assert got["run_s"] == pytest.approx(1.0)
    assert got["gang_traces"] == 1.0
    # study spans cover 10 + 8 s, less gang spans 6 + 4 s
    assert got["engine_span_s"] == pytest.approx((4 + 4) / 2)
    assert scopes.study_spans(spans, 4) is None
    assert scopes.study_spans(spans, 0) is None


def test_recorded_trace_idle_and_leads():
    prof = scopes.read_profile(str(SMALL))
    assert prof["ops"] and prof["lo"] < prof["hi"]
    idle = scopes.idle_unnamed(prof)
    busy = sum(e - s for s, e in trace.merge(
        [(s, e) for s, e, _ in prof["ops"]])) * 1e-9
    # no papas. span in the recording: every idle second is unnamed
    assert idle == pytest.approx((prof["hi"] - prof["lo"]) * 1e-9 - busy)
    lead = scopes.leads(prof, "bench.step")
    assert len(lead) == 3
    assert all(abs(x) < 5.0 for x in lead)
    assert scopes.leads(prof, "bench.no-such-span") == []


def test_traced_study_run_reports_the_span_metrics(monkeypatch):
    import jax
    from repro.train import ensemble
    import dataclasses
    smoke = ensemble.get_smoke
    monkeypatch.setattr(ensemble, "get_smoke", lambda arch: dataclasses.replace(
        smoke(arch), compute_dtype="float32"))
    from benchmarks.chip import harness
    monkeypatch.setattr(harness, "load", checks.shrunk(harness.load))
    entries = run.cell_entries(checks.bench(), checks.STUDY_CELL)
    line = run.measure(entries, seed=2**32 + 5, seconds=0.3, trace=True,
                       devices=jax.devices()[:1], t0=time.perf_counter())
    metrics = line["metrics"]
    for name in ("study_lower_s", "study_load_s", "study_run_s",
                 "study_gang_traces", "study_engine_span_s"):
        assert metrics[name]["value"] >= 0, name
    assert metrics["study_gang_traces"]["value"] == 1.0
    assert metrics["study_lower_s"]["value"] > 0
    assert line["correct"] is True, line["checks"]
