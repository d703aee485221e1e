#!/usr/bin/env python3
"""Readings that the limits of a cell's checks are set from.

    python3 benchmarks/chip/calibrate.py --workload <cell> \\
        --seeds 1,2,...,12 --control-seeds 1,2,3

For every seed, the numbers the cell compares, read from the program
at the cell's own size: for a train cell set-up and the checked steps;
for a study cell a whole run, the measured window of ``--seconds``
(``run_seconds`` by default) included, every member of every study in
it compared. For every control seed, the same numbers from the control,
which is the plain reference in the program's place computed with
float8 matmul inputs, and from the faults planted in the reference put
in the program's place, over the same steps or members: half of each
batch left out (the mean taken over the rest), and, for a study, every
member's answer swapped with its neighbour's, one member's answer
swapped so, and members whose steps leave their state unchanged. One
JSON line per reading. The benchmark's own runs never run this.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]


def train_readings(spec: dict, control: bool) -> list[dict]:
    from benchmarks.chip import compare
    from benchmarks.chip.paths import train
    trainer = train.Trainer(spec)
    program = trainer.checked_steps()
    abstract = trainer.abstract_params
    trainer.free()
    ref = train.reference(spec, abstract)
    out = [{"reading": "program", **compare.train_readings(program, ref)}]
    if control:
        half = spec["traffic"]["batch"] // 2
        for name, kw in (("control", {"lowp": "fp8"}),
                         ("fault_half_batch", {"rows": half})):
            other = train.reference(spec, abstract, **kw)
            out.append({"reading": name,
                        **compare.train_readings(other, ref)})
    return out


def study_readings(spec: dict, control: bool) -> list[dict]:
    import numpy as np
    from benchmarks.chip.paths import study
    out = study.run(spec)
    answers, ref = out["answers"], out["reference"]

    def reading(name, got):
        gaps = study.member_gaps(got, ref)
        by_lr = {}
        for a, g in zip(got, gaps):
            by_lr.setdefault(a[0], []).append(g)
        return {"reading": name, "member_gap_median": study.loss_gap(got, ref),
                "member_gap_max": float(np.max(gaps)), "members": len(got),
                "median_by_lr": {str(k): float(np.median(v))
                                 for k, v in sorted(by_lr.items())}}

    def replaced(values):
        return [a[:3] + (float(v),) for a, v in zip(answers, values)]

    res = [reading("program", answers)]
    if control:
        tr = spec["traffic"]
        one = int(np.random.default_rng(spec["seed"]).integers(len(answers)))
        nxt = answers[1:] + answers[:1]
        still = [(0.0,) + a[1:] for a in answers]
        for name, got in (
                ("control", replaced(study.reference(spec, answers,
                                                     lowp="fp8"))),
                ("fault_half_batch", replaced(study.reference(
                    spec, answers, rows=tr["batch"] // 2))),
                ("fault_answers_altered",
                 [a[:3] + (b[3],) for a, b in zip(answers, nxt)]),
                ("fault_one_answer_altered",
                 [a[:3] + ((nxt[i][3],) if i == one else (a[3],))
                  for i, a in enumerate(answers)]),
                ("fault_state_unchanged", replaced(study.reference(
                    spec, still)))):
            res.append(reading(name, got))
    return res


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, default=None)
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from benchmarks.chip import harness, run
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = run.cell_entries(bench, args.workload)
    seconds = (bench["run_seconds"] if args.seconds is None
               else args.seconds)
    cell = entries["cell"]
    devices = run.require_chips(cell["chips"])
    config = harness.load("configs", cell["config"])
    traffic = harness.load("traffic", cell["traffic"])
    readings = {"train": train_readings, "study": study_readings}[
        traffic["kind"]]
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        spec = {"config": config, "traffic": traffic, "seed": seed,
                "seconds": seconds, "trace": False, "devices": devices,
                "t0": time.perf_counter()}
        for r in readings(spec, seed in controls):
            print(json.dumps({"workload": args.workload, "seed": seed, **r,
                              "elapsed_s": time.perf_counter() - T0}),
                  flush=True)


if __name__ == "__main__":
    main()
