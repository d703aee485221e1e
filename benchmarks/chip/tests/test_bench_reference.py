"""The plain references against the program, on the CPU at small sizes,
with the program computing in float32 so that the two must agree to
float32 rounding: a decoder with a window shorter and longer than the
sequence, the Mamba-2 SSD layer (sequential recurrence against the
program's chunked scan), and a study member's whole training run."""
import json
import subprocess
import sys
import time

import pytest

from benchmarks.chip import harness
from benchmarks.chip.tests import tiny

#: float32 agreement: sums in another order, over a few steps
TIGHT = 1e-5


@pytest.mark.parametrize("model", [
    tiny.DANUBE,                                  # window 16 < seq 64
    {**tiny.DANUBE, "window": 4096},              # plain causal
    tiny.MAMBA2,
], ids=["swa", "causal", "ssd"])
def test_train_step_matches_reference(model):
    from benchmarks.chip import compare
    from benchmarks.chip.paths import train
    spec = tiny.train_spec({**model, "compute_dtype": "float32"})
    trainer = train.Trainer(spec)
    program = trainer.checked_steps()
    abstract = trainer.abstract_params
    trainer.free()
    ref = train.reference(spec, abstract)
    readings = compare.train_readings(program, ref)
    assert max(readings.values()) < TIGHT, readings
    assert program["grad_norm"] == pytest.approx(ref["grad_norm"], rel=TIGHT)


def test_study_member_matches_reference(monkeypatch):
    import dataclasses

    import numpy as np
    from repro.train import ensemble
    from benchmarks.chip.paths import study
    smoke = ensemble.get_smoke
    monkeypatch.setattr(ensemble, "get_smoke", lambda arch: dataclasses.replace(
        smoke(arch), compute_dtype="float32"))
    spec = {"config": harness.load("configs", "study-lr-seed-25"),
            "traffic": {**harness.load("traffic", "study-gang-25"),
                        "steps": 4, "batch": 2, "seq": 32}}
    tr = spec["traffic"]
    members = [{"args:lr": lr, "args:seed": seed, "args:arch":
                spec["config"]["member_arch"], "args:steps": tr["steps"],
                "args:batch": tr["batch"], "args:seq": tr["seq"]}
               for lr, seed in [(1e-2, 3), (3e-3, 2**31 - 2)]]
    got = ensemble.train_ensemble(members)
    answers = [(m["args:lr"], m["args:seed"], "ok", g)
               for m, g in zip(members, got)]
    ref = study.reference(spec, answers)
    assert study.loss_gap(answers, ref) < TIGHT
    assert np.all(np.isfinite(ref))


def test_references_import_nothing_of_the_program():
    code = ("import sys; import benchmarks.chip.reference.model, "
            "benchmarks.chip.reference.member; "
            "print(sorted(m for m in sys.modules if m.startswith('repro')))")
    out = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, cwd=str(tiny.ROOT), check=True,
                         env={**tiny.cpu_env(), "PYTHONPATH": str(tiny.ROOT)})
    assert json.loads(out.stdout.strip().replace("'", '"')) == []
