"""Named device work: the model step's ``jax.named_scope`` per layer
kind, and the study path's ``papas.*`` spans (``core/telemetry.span``)
on the profiler's clock and in an armed run's ``trace.json``."""
import collections
import glob
import json
import os
import re
import subprocess
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import scopes  # noqa: E402
from repro.core import telemetry  # noqa: E402

GANG = ["papas.gang.build", "papas.gang.lower", "papas.gang.compile",
        "papas.gang.run"]
STUDY = ["papas.study.plan", "papas.study.schedule", "papas.study.record"]
MEMBER = {"args:arch": "h2o-danube-1.8b", "args:seed": 0, "args:steps": 2,
          "args:batch": 1, "args:seq": 16}


@pytest.mark.parametrize("arch, want", [
    ("h2o-danube-1.8b", {"embed", "mixer", "ffn", "head", "optimizer"}),
    ("mamba2-780m", {"embed", "mixer", "head", "optimizer"}),
])
def test_train_step_carries_every_scope(arch, want):
    from repro.configs import get_smoke
    from repro.optim.adamw import AdamW, cosine_schedule
    from repro.train.step import init_train_state, make_train_step
    cfg = get_smoke(arch)
    opt = AdamW(schedule=cosine_schedule(1e-3, 1, 10))
    state = jax.eval_shape(lambda k: init_train_state(cfg, opt, k),
                           jax.random.PRNGKey(0))
    tok = jax.ShapeDtypeStruct((2, 32), jnp.int32)
    text = jax.jit(make_train_step(cfg, opt)).lower(
        state, {"tokens": tok, "labels": tok}).compile().as_text()
    assert set(scopes.scope_map(text).values()) - {None} == want


def test_matcher_assigns_checkpoint_and_backward_ops():
    def f(w, x):
        with jax.named_scope("mixer"):
            y = jax.checkpoint(lambda v: jnp.tanh(v @ w))(x)
        return jnp.sum(y * y)

    text = jax.jit(jax.grad(f)).lower(jnp.ones((8, 8)),
                                       jnp.ones((4, 8))).compile().as_text()
    op_names = dict(re.findall(r'^\s+(?:ROOT\s+)?%?([\w.\-]+) = .*'
                               r'op_name="([^"]*)"', text, re.M))
    backward = [n for n, o in op_names.items()
                if "transpose(jvp(mixer))" in o]
    remat = [n for n, o in op_names.items() if "rematted_computation" in o]
    assert backward and remat, op_names
    found = scopes.scope_map(text)
    assert {found[n] for n in backward + remat} == {"mixer"}


def test_gang_spans_on_the_profiler_host_plane(tmp_path):
    from jax.profiler import ProfileData
    from repro.train.ensemble import train_ensemble
    with jax.profiler.trace(str(tmp_path)):
        losses = train_ensemble([{**MEMBER, "args:lr": lr}
                                 for lr in (1e-3, 3e-3)])
    assert len(losses) == 2 and np.all(np.isfinite(losses))
    path, = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    names = [ev.name for plane in ProfileData.from_file(path).planes
             if plane.name == "/host:CPU" for line in plane.lines
             for ev in line.events if ev.name.startswith("papas.")]
    assert sorted(names) == sorted(GANG)


def test_armed_gang_sweep_spans_and_histograms(tmp_path, monkeypatch):
    from repro.launch import mesh, sweep
    monkeypatch.setattr(mesh, "enable_compile_cache", lambda: None)
    wdl = tmp_path / "s.yaml"
    wdl.write_text("study:\n  args:\n    lr: [0.001, 0.002]\n"
                   "    arch: [h2o-danube-1.8b]\n    steps: [2]\n"
                   "    batch: [1]\n    seq: [16]\n  command: train\n")
    out = sweep.main([str(wdl), "--root", str(tmp_path / "root"), "--gang",
                      "--trace"])
    assert out["dispatches"] == 1
    assert telemetry.current() is None
    study_dir, = (tmp_path / "root").iterdir()
    doc = json.loads((study_dir / "trace.json").read_text())
    spans = collections.Counter(e["name"] for e in doc["traceEvents"]
                                if e.get("cat") == "span" and e["ph"] == "B")
    assert spans == collections.Counter(STUDY + GANG)
    snapshot = json.loads((study_dir / "study.json").read_text())["telemetry"]
    counts = {k.split('"')[1]: v["count"] for k, v in snapshot.items()
              if k.startswith("papas_span_seconds{")}
    assert counts == dict(spans)
    assert snapshot["groupcommit_shards"]["records"]
    offset = doc["otherData"]["profiler_clock_offset_us"] * 1e-6
    assert abs(offset - (time.time() - time.monotonic())) < 5.0


def test_armed_shell_study_spans_without_jax(tmp_path):
    wdl = tmp_path / "s.yaml"
    wdl.write_text('work:\n  args:\n    n: ["1:3"]\n'
                   '  command: sh -c "echo ${args:n}"\n')
    code = ("import json, sys\nfrom pathlib import Path\n"
            "from repro.launch import sweep\n"
            f"root = Path({str(tmp_path / 'root')!r})\n"
            f"sweep.main([{str(wdl)!r}, '--root', str(root), '--trace'])\n"
            "assert 'jax' not in sys.modules\n"
            "doc = json.loads(next(root.glob('*/trace.json')).read_text())\n"
            "print(sorted(e['name'] for e in doc['traceEvents']\n"
            "             if e.get('cat') == 'span' and e['ph'] == 'B'))\n")
    r = subprocess.run([sys.executable, "-c", code],
                       env={**os.environ,
                            "JAX_PLATFORMS": "cpu",
                            "PYTHONPATH": str(ROOT / "src")},
                       capture_output=True, text=True, timeout=300)
    assert r.returncode == 0, r.stderr
    assert r.stdout.strip().splitlines()[-1] == repr(sorted(STUDY))


def test_disarmed_span_is_kept_in_memory_only():
    assert telemetry.current() is None
    with telemetry.span("test.outer"):
        with telemetry.span("test.inner"):
            pass
    last = telemetry.recent_spans()[-2:]
    assert [n for n, _, _ in last] == ["papas.test.inner", "papas.test.outer"]
    (_, s_in, e_in), (_, s_out, e_out) = last
    assert s_out <= s_in <= e_in <= e_out
