#!/usr/bin/env python3
"""Readings that the limits of a ``train_sharded`` cell are set from.

    python3 benchmarks/chip/calibrate_sharded.py --workload <cell> \\
        --seeds 1,2,3 --control-seeds 1

What ``calibrate.py`` reads for a one-chip train cell, with the plain
reference spread over the cell's chips (``paths/train_sharded.py``):
for every seed, the numbers the cell compares, read from the program's
set-up and checked steps; for every control seed, the same numbers from
the control (the reference in the program's place, with float8 matmul
inputs) and from the fault of half of each batch left out. One JSON
line per reading. The benchmark's own runs never run this.
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


def readings(spec: dict, control: bool) -> list[dict]:
    from benchmarks.chip import compare
    from benchmarks.chip.paths import train_sharded
    trainer = train_sharded.Trainer(spec)
    program = trainer.checked_steps()
    abstract = trainer.abstract_params
    trainer.free()
    ref = train_sharded.reference(spec, abstract)
    out = [{"reading": "program", **compare.train_readings(program, ref)}]
    if control:
        half = spec["traffic"]["batch"] // 2
        for name, kw in (("control", {"lowp": "fp8"}),
                         ("fault_half_batch", {"rows": half})):
            other = train_sharded.reference(spec, abstract, **kw)
            out.append({"reading": name,
                        **compare.train_readings(other, ref)})
    return out


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args()
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(ROOT / ".jax_cache"))
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    from benchmarks.chip import harness, run
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    cell = run.cell_entries(bench, args.workload)["cell"]
    devices = run.require_chips(cell["chips"])
    config = harness.load("configs", cell["config"])
    traffic = harness.load("traffic", cell["traffic"])
    if traffic["kind"] != "train_sharded":
        raise SystemExit(f"{args.workload} is not a train_sharded cell; "
                         f"calibrate.py reads its limits")
    controls = {int(s) for s in args.control_seeds.split(",") if s}
    for seed in (int(s) for s in args.seeds.split(",")):
        spec = {"config": config, "traffic": traffic, "seed": seed,
                "seconds": 0.0, "trace": False, "devices": devices,
                "t0": time.perf_counter()}
        for r in readings(spec, seed in controls):
            print(json.dumps({"workload": args.workload, "seed": seed, **r,
                              "elapsed_s": time.perf_counter() - T0}),
                  flush=True)


if __name__ == "__main__":
    main()
