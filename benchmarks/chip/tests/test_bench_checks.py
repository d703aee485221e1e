"""The checks that decide ``correct`` fail where they must.

The control (the reference in the program's place, computed with
float8 matmul inputs, one precision step below the configuration's
bfloat16) must fail a cell's limits. And a whole run, with the look
for a chip skipped and the timed path broken underneath, must report
``correct: false``: a train step that returns its state unchanged, a
step that leaves out half of its batch, a study whose answers are
altered where they are produced, and a study whose members train on
half of each batch.
"""
import json
import time

import pytest

from benchmarks.chip import compare, harness, run
from benchmarks.chip.tests import tiny

TRAIN_CELLS = {"danube-l4-train-4x2048": tiny.DANUBE,
               "mamba2-l24-train-4x2048": tiny.MAMBA2}
STUDY_CELL = "study-lr-seed-25-gang"


def bench():
    return json.loads((tiny.ROOT / "BENCHMARK.json").read_text())


@pytest.mark.parametrize("cell", sorted(TRAIN_CELLS))
def test_train_control_fails_the_limits(cell):
    from benchmarks.chip.paths import train
    spec = tiny.train_spec(TRAIN_CELLS[cell], batch=4, seq=128)
    trainer = train.Trainer(spec)
    abstract = trainer.abstract_params
    trainer.free()
    ref = train.reference(spec, abstract)
    control = train.reference(spec, abstract, lowp="fp8")
    readings = compare.train_readings(control, ref)
    correct, checks = compare.judge(readings, harness.load("limits", cell))
    assert not correct, checks


def test_study_control_fails_the_limits():
    from benchmarks.chip.paths import study
    spec = {"config": harness.load("configs", "study-lr-seed-25"),
            "traffic": harness.load("traffic", "study-gang-25")}
    lrs, seeds = study.members(5, 0, spec["traffic"])
    answers = [(lr, s, "ok", 0.0) for lr in lrs for s in seeds]
    ref = study.reference(spec, answers)
    ctl = study.reference(spec, answers, lowp="fp8")
    gap = study.loss_gap([a[:3] + (float(c),) for a, c in zip(answers, ctl)],
                         ref)
    assert gap > harness.load("limits", STUDY_CELL)["member_gap_median"]


def shrunk(load):
    """``harness.load`` with the cells' sizes cut for the CPU, and the
    program computing in float32: at these sizes a sound bfloat16 run
    can read above the limits set at the cells' own sizes, while a
    float32 one agrees with the reference to rounding."""
    def inner(kind, name):
        d = load(kind, name)
        if kind == "configs" and "model" in d:
            d["model"] = dict(TRAIN_CELLS[next(
                c for c, w in ((w["name"], w) for w in bench()["workloads"])
                if w["config"] == name)], compute_dtype="float32")
        elif kind == "configs":
            d["members"] = 4
        elif kind == "traffic" and d["kind"] == "train":
            d.update(batch=2, seq=64)
        elif kind == "traffic":
            d.update(lrs=d["lrs"][-2:], n_seed=2, steps=4, batch=2, seq=32)
        return d
    return inner


def measure(monkeypatch, cell):
    import dataclasses

    import jax
    from repro.train import ensemble
    smoke = ensemble.get_smoke
    monkeypatch.setattr(ensemble, "get_smoke", lambda arch: dataclasses.replace(
        smoke(arch), compute_dtype="float32"))
    monkeypatch.setattr(harness, "load", shrunk(harness.load))
    return run.measure(run.cell_entries(bench(), cell), seed=2**32 + 3,
                       seconds=0.3, trace=False, devices=jax.devices()[:1],
                       t0=time.perf_counter())


def break_train_step(monkeypatch, broken):
    from repro.train import step as step_mod
    make = step_mod.make_train_step

    def make_broken(cfg, opt, *a, **k):
        return broken(make(cfg, opt, *a, **k))
    monkeypatch.setattr(step_mod, "make_train_step", make_broken)


def unchanged(step):
    def fn(state, batch):
        _, metrics = step(state, batch)
        return state, metrics
    return fn


def half_batch(step):
    def fn(state, batch):
        half = batch["tokens"].shape[0] // 2
        return step(state, {k: v[:half] for k, v in batch.items()})
    return fn


@pytest.mark.parametrize("cell", sorted(TRAIN_CELLS))
@pytest.mark.parametrize("broken", [unchanged, half_batch])
def test_broken_train_step_is_not_correct(monkeypatch, cell, broken):
    break_train_step(monkeypatch, broken)
    line = measure(monkeypatch, cell)
    assert line["correct"] is False, line["checks"]


def test_sound_runs_are_correct(monkeypatch):
    for cell in [*sorted(TRAIN_CELLS), STUDY_CELL]:
        line = measure(monkeypatch, cell)
        assert line["correct"] is True, (cell, line["checks"])
        assert list(line)[-1] == "checks"


def altered(gang):
    def fn(members):
        values = gang(members)
        return values[1:] + values[:1]
    return fn


def half_batch_members(gang):
    def fn(members):
        return gang([{**m, "args:batch": int(m["args:batch"]) // 2}
                     for m in members])
    return fn


@pytest.mark.parametrize("broken", [altered, half_batch_members])
def test_broken_study_is_not_correct(monkeypatch, broken):
    from repro.train import ensemble
    monkeypatch.setattr(ensemble, "train_ensemble",
                        broken(ensemble.train_ensemble))
    line = measure(monkeypatch, STUDY_CELL)
    assert line["correct"] is False, line["checks"]
