"""Study path: whole parameter studies through the PaPaS sweep.

Each study is one call of ``repro.launch.sweep.main([wdl, "--root",
<fresh directory>, "--gang"])``: WDL parsing, the parameter space, the
scheduler, the gang pool, ``train/ensemble.py:train_ensemble`` and the
study's journal and records. The WDL is written from the traffic file:
its ``lrs`` and ``n_seed`` member seeds drawn from
``SeedSequence([seed, study])``, so every study trains the same learning
rates, and does the same work, whatever the seed.

Set-up runs study 0 (which compiles, or loads the gang program from the
persistent cache). The window runs studies 1, 2, ... back to back,
starting a new one while less than ``--seconds`` have passed, and ends
when the last one started has finished. ``study_makespan_s`` is the
window over the number of studies in it.

Spans: ``bench.sweep`` around ``sweep.main`` and ``bench.gang`` around
the gang call, which the benchmark wraps for the run (the module
attribute the sweep looks up at call time). ``study_engine_s`` is the
first less the second; ``study_compile_s`` is JAX's own count of the
seconds spent tracing, lowering and compiling (cache loads included).

Afterwards the plain reference trains every member of every study in
float32 and each recorded final loss is compared with it.
"""
from __future__ import annotations

import contextlib
import io
import json
import shutil
import tempfile
import time
from pathlib import Path
from typing import Any

import numpy as np

from .. import flops, harness
from ..reference import member as ref_member

WDL = """\
study:
  args:
    lr: {lrs}
    seed: {seeds}
    arch: [{arch}]
    steps: [{steps}]
    batch: [{batch}]
    seq: [{seq}]
  command: {command}
"""


def members(seed: int, study: int, tr: dict) -> tuple[list, list]:
    """The learning rates and member seeds of one study."""
    rng = np.random.default_rng(np.random.SeedSequence([seed, study]))
    seeds = [int(x) for x in rng.integers(0, 2**31 - 1, tr["n_seed"])]
    return [float(x) for x in tr["lrs"]], seeds


class Studies:
    """Runs studies through the sweep and keeps what each recorded."""

    def __init__(self, spec: dict, tmp: Path) -> None:
        from repro.train import ensemble
        self.spec, self.tmp, self.ensemble = spec, tmp, ensemble
        self.tr, self.conf = spec["traffic"], spec["config"]
        if len(self.tr["lrs"]) * self.tr["n_seed"] != self.conf["members"]:
            raise ValueError("traffic and configuration disagree on the "
                             "number of members")
        self.gang_s = self.sweep_s = 0.0
        self.answers: list[tuple[float, int, str, Any]] = []
        self._gang = ensemble.train_ensemble

    def _timed_gang(self, group):
        t0 = time.perf_counter()
        with harness.span("gang"):
            out = self._gang(group)
        self.gang_s += time.perf_counter() - t0
        return out

    def __enter__(self):
        self.ensemble.train_ensemble = self._timed_gang
        return self

    def __exit__(self, *exc):
        self.ensemble.train_ensemble = self._gang

    def run(self, index: int) -> None:
        from repro.launch import sweep
        lrs, seeds = members(self.spec["seed"], index, self.tr)
        tr = self.tr
        d = self.tmp / f"study{index}"
        d.mkdir()
        wdl = d / "study.yaml"
        wdl.write_text(WDL.format(
            lrs=json.dumps(lrs), seeds=json.dumps(seeds),
            arch=self.conf["member_arch"], steps=tr["steps"],
            batch=tr["batch"], seq=tr["seq"], command=tr["command"]))
        t0 = time.perf_counter()
        with contextlib.redirect_stdout(io.StringIO()), harness.span("sweep"):
            out = sweep.main([str(wdl), "--root", str(d / "root"), "--gang"])
        self.sweep_s += time.perf_counter() - t0
        combos = {}
        for rec in (d / "root").glob("*/records.jsonl"):
            for line in rec.read_text().splitlines():
                r = json.loads(line)
                combos[r["task_id"]] = r["combo"]
        for rid, res in out["results"].items():
            c = combos.get(rid) or {}
            self.answers.append((float(c.get("args:lr", c.get("lr", "nan"))),
                                 int(c.get("args:seed", c.get("seed", -1))),
                                 res.status, res.value))
        shutil.rmtree(d)


def reference(spec: dict, answers, lowp: str | None = None,
              rows: int | None = None) -> np.ndarray:
    """The reference's final loss of every answered member."""
    import jax
    import jax.numpy as jnp
    tr, m = spec["traffic"], spec["config"]["member_model"]
    fn = jax.jit(jax.vmap(lambda lr, seed: ref_member.final_loss(
        m, lr, seed, steps=tr["steps"], batch=tr["batch"], seq=tr["seq"],
        adamw=tr["adamw"], rows=rows, lowp=lowp)))
    lrs = jnp.asarray([a[0] for a in answers], jnp.float32)
    seeds = jnp.asarray([a[1] for a in answers], jnp.int32)
    with jax.default_matmul_precision("highest"):
        return np.asarray(fn(lrs, seeds), np.float64)


def member_gaps(answers, ref: np.ndarray) -> np.ndarray:
    """Relative gap of each recorded final loss; infinite for a member
    that recorded no finite loss."""
    got = np.asarray([a[3] if a[2] == "ok" and a[3] is not None
                      else np.inf for a in answers], np.float64)
    gaps = np.abs(got - ref) / np.abs(ref)
    return np.where(np.isfinite(got), gaps, np.inf)


def loss_gap(answers, ref: np.ndarray) -> float:
    """The median member gap; infinite where any member has none.

    The median, not the worst member: 20 AdamW steps at the highest
    rates amplify any rounding, so the worst member reads about
    1e-3 in bfloat16 and in float8 alike, while the median separates
    them (see PERF.md)."""
    gaps = member_gaps(answers, ref)
    return (float(np.median(gaps)) if np.all(np.isfinite(gaps))
            else float("inf"))


def run(spec: dict) -> dict[str, Any]:
    tr, conf = spec["traffic"], spec["config"]
    compiles = harness.Compiles()
    found: dict[str, Any] = {}
    with tempfile.TemporaryDirectory(prefix="bench_study_") as tmp, \
            Studies(spec, Path(tmp)) as studies:
        studies.run(0)
        setup_s = time.perf_counter() - spec["t0"]
        gang0, sweep0, n = studies.gang_s, studies.sweep_s, 0
        compiles.counting = True
        with harness.traced(spec["trace"], found), harness.span("window"):
            t0 = time.perf_counter()
            while n == 0 or time.perf_counter() - t0 < spec["seconds"]:
                n += 1
                studies.run(n)
            window_s = time.perf_counter() - t0
        compiles.counting = False
        compiles.close()
        engine_s = (studies.sweep_s - sweep0) - (studies.gang_s - gang0)
        answers = studies.answers
    peak = harness.memory_peak(spec["devices"])
    ref = reference(spec, answers)
    makespan = window_s / n
    member_flops = (conf["members"] * tr["steps"] * tr["batch"] * tr["seq"]
                    * flops.train_flops_per_token(conf["member_model"],
                                                  tr["seq"]))
    return {
        "end_to_end": {"study_makespan_s": makespan, "setup_s": setup_s},
        "context": {"makespan_s": makespan, "window_studies": n,
                    "window_s": window_s,
                    "study_compile_s": compiles.seconds / n,
                    "study_engine_s": engine_s / n,
                    "study_flops": member_flops,
                    "trace": found.get("trace")},
        "attempted": len(answers),
        "failed": sum(1 for a in answers if a[2] != "ok"),
        "readings": {"member_gap_median": loss_gap(answers, ref),
                     "window_cache_misses": compiles.misses},
        "memory_peak_bytes": peak,
        "trace": found.get("trace"),
        "answers": answers,
        "reference": ref,
    }
