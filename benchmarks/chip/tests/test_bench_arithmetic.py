"""The yardstick's arithmetic: peaks, model FLOPs, whole-window ratios."""
import time

import pytest

from benchmarks.chip import flops, harness, peaks


def test_unknown_device_kind_raises():
    assert peaks.peaks("TPU v5 lite")["flops_bf16"] == 197e12
    with pytest.raises(KeyError, match="no published peaks"):
        peaks.peaks("TPU v9 imaginary")


def test_mean_causal_context():
    assert flops.mean_causal_context(4) == pytest.approx(2.5)
    assert flops.mean_causal_context(2048, 4096) == pytest.approx(1024.5)
    # window 2 over 4 positions: 1, 2, 2, 2 keys
    assert flops.mean_causal_context(4, 2) == pytest.approx(7 / 4)


@pytest.mark.parametrize("config,matmul,per_token", [
    # 4 x (16.4 M attention + 53.1 M SwiGLU) + 81.9 M head; attention at
    # a mean causal context of 1024.5 adds 0.126 GFLOP a token (24
    # layers: 0.755)
    ("h2o-danube-1.8b-l4", 359_792_640, 2_284_646_400.0),
    ("h2o-danube-1.8b-tp4", 1_749_155_840, 11_250_278_400.0),
    # 24 x 14.6 M SSD projections + 77.2 M tied head; the chunked scan
    # at Q = 256 and the conv add 0.286 GFLOP a token
    ("mamba2-780m-l24", 428_175_360, 2_854_748_160.0),
])
def test_model_flops_per_token(config, matmul, per_token):
    if config == "h2o-danube-1.8b-tp4":     # full depth, no cell yet
        m = dict(harness.load("configs", "h2o-danube-1.8b-l4")["model"],
                 n_layers=24, layer_types=["swa"] * 24)
    else:
        m = harness.load("configs", config)["model"]
    assert flops.matmul_params(m) == matmul
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(per_token)


def test_study_makespan_is_the_whole_window(monkeypatch):
    """Studies of unequal length: the window over their number, not a
    median of per-study times."""
    from benchmarks.chip.paths import study
    walls = iter([0.05, 0.05, 0.25, 0.05, 0.05, 0.05])

    def fake_run(self, index):
        time.sleep(next(walls))
        self.answers.append((1e-3, index, "ok", 1.0))

    monkeypatch.setattr(study.Studies, "run", fake_run)
    monkeypatch.setattr(study, "reference", lambda spec, answers: [1.0] * len(answers))
    spec = {"config": harness.load("configs", "study-lr-seed-25"),
            "traffic": harness.load("traffic", "study-gang-25"),
            "seed": 1, "seconds": 0.2, "trace": False, "devices": [],
            "t0": time.perf_counter()}
    out = study.run(spec)
    ctx = out["context"]
    assert ctx["window_studies"] >= 2
    assert out["end_to_end"]["study_makespan_s"] == pytest.approx(
        ctx["window_s"] / ctx["window_studies"])
    assert out["end_to_end"]["study_makespan_s"] > 0.06
