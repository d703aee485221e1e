#!/usr/bin/env python3
"""One run of one benchmark cell, on the chip it is started on.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are found by
name: ``BENCHMARK.json`` at the root of the checkout names them,
``configs/<config>.json``, ``traffic/<traffic>.json`` and
``limits/<cell>.json`` hold them, ``paths/<traffic kind>.py`` runs them
and ``metrics/<metric>.py`` reads each per-layer metric. A later cell
or metric is new files and new entries.

Only a TPU is measured: with no TPU, or fewer chips than the cell asks
for, the run exits non-zero before it measures anything. The last line
of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics with
``--trace 0``, its per-layer metrics with ``--trace 1``), ``device``,
with ``--trace 1`` the ``breakdown``, and last ``checks``: each number
compared beside its limit, which also end standard error.
"""
import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]
HERE = Path(__file__).resolve().parent
#: the persistent compilation cache, at a fixed path inside the checkout
#: (the path is part of the cache key); JAX_COMPILATION_CACHE_DIR wins
CACHE_DIR = ROOT / ".jax_cache"


def cell_entries(bench: dict, name: str) -> dict:
    """The cell ``name`` with its end-to-end and per-layer metrics."""
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"run.py: no workload {name!r} in BENCHMARK.json")
    cell = cells[name]
    e2e = [m for m in bench["end_to_end"]
           if name in m.get("workloads", [name])]
    names = {m["name"] for m in e2e}
    layer = [m for m in bench["per_layer"]
             if (name in m["workloads"] if "workloads" in m
                 else m["moves"] in names)]
    return {"cell": cell, "end_to_end": e2e, "per_layer": layer}


def require_chips(n: int):
    """The first ``n`` TPU devices; exits non-zero where there are none."""
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < n:
        print(f"run.py: the cell needs {n} TPU chip(s); JAX reports "
              f"{len(devices)} {devices[0].platform} device(s). Nothing "
              f"is measured off the chip.", file=sys.stderr)
        raise SystemExit(3)
    return devices[:n]


def reader(name: str):
    """``metrics/<name>.py``'s ``read``."""
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location(
        f"benchmarks.chip.metrics.{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def measure(entries: dict, *, seed: int, seconds: float, trace: bool,
            devices, t0: float) -> dict:
    """Run the cell and build the result line (without printing it)."""
    from benchmarks.chip import compare, harness, peaks
    cell = entries["cell"]
    config = harness.load("configs", cell["config"])
    traffic = harness.load("traffic", cell["traffic"])
    limits = harness.load("limits", cell["name"])
    path = importlib.import_module(f"benchmarks.chip.paths.{traffic['kind']}")
    kind = devices[0].device_kind
    out = path.run({"config": config, "traffic": traffic, "seed": seed,
                    "seconds": seconds, "trace": trace, "devices": devices,
                    "t0": t0})
    correct, checks = compare.judge(out["readings"], limits)
    if trace:
        ctx = {**out["context"], "chips": len(devices),
               "peak_flops": peaks.peaks(kind)["flops_bf16"]
               if devices[0].platform == "tpu" else None}
        metrics = {}
        for m in entries["per_layer"]:
            value = reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": out["end_to_end"][m["name"]],
                               "unit": m["unit"]}
                   for m in entries["end_to_end"]}
    device = {"platform": devices[0].platform, "kind": kind,
              "count": len(devices),
              "memory_peak_bytes": out["memory_peak_bytes"]}
    line = {"correct": correct, "attempted": out["attempted"],
            "failed": out["failed"], "metrics": metrics, "device": device}
    found = out.get("trace")
    if trace and found:
        device["busy_s"] = found["busy_s"]
        device["window_s"] = found["window_s"]
        line["breakdown"] = {"device_ops": found["device_ops"],
                             "idle_gaps": found["idle_gaps"]}
    line["checks"] = checks
    return line


def finite(x):
    """JSON has no inf or nan: such a number is written as a string."""
    if isinstance(x, dict):
        return {k: finite(v) for k, v in x.items()}
    if isinstance(x, list):
        return [finite(v) for v in x]
    if isinstance(x, float) and not math.isfinite(x):
        return str(x)
    return x


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    entries = cell_entries(bench, args.workload)
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR", str(CACHE_DIR))
    sys.path[:0] = [str(ROOT / "src"), str(ROOT)]
    import jax
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    devices = require_chips(entries["cell"]["chips"])
    line = finite(measure(entries, seed=args.seed, seconds=args.seconds,
                          trace=bool(args.trace), devices=devices, t0=T0))
    for name, c in line["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


if __name__ == "__main__":
    main()
