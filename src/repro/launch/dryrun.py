"""Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell this produces, WITHOUT allocating any real tensors:
  * proof the sharded program compiles (SPMD partitioning is coherent),
  * ``memory_analysis()``  — bytes/device (fits-in-HBM check),
  * ``cost_analysis()``    — per-device HLO FLOPs + bytes accessed,
  * the collective schedule parsed from the post-SPMD HLO text
    (all-gather / all-reduce / reduce-scatter / all-to-all /
    collective-permute operand bytes),
  * three-term roofline (compute / memory / collective seconds) against
    the published peaks of ``MODELED_KIND`` (``repro.launch.mesh.PEAKS``).

Results are written one JSON per cell under experiments/dryrun/.
"""
# The placeholder-device flag MUST be set before jax initializes devices —
# keep these as the very first executable statements of the module.
import os  # noqa: E402
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", ""))

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import json  # noqa: E402
import re  # noqa: E402
import time  # noqa: E402
from pathlib import Path  # noqa: E402

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import all_archs, get  # noqa: E402
from repro.distributed import sharding as shd  # noqa: E402
from repro.launch.mesh import (  # noqa: E402
    auto_mesh, make_production_mesh, mesh_chips, peaks,
)
from repro.models.config import SHAPES, cell_applicable  # noqa: E402
from repro.models.model import cache_specs, input_specs  # noqa: E402
from repro.optim.adamw import AdamW, cosine_schedule  # noqa: E402
from repro.serve.engine import make_serve_step  # noqa: E402
from repro.train.step import (  # noqa: E402
    TrainStepConfig, abstract_train_state, make_train_step,
)

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "bf16": 2, "f16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "s32": 4, "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2,
    "u8": 1, "pred": 1, "c64": 8, "c128": 16,
}

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

#: effective bytes crossing a link per payload byte (ring algorithms)
_ALGO_FACTOR = {
    "all-gather": 1.0, "all-reduce": 2.0, "reduce-scatter": 1.0,
    "all-to-all": 1.0, "collective-permute": 1.0,
}

#: the device kind whose peaks the roofline uses (TPU v5e)
MODELED_KIND = "TPU v5 lite"

_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _shape_bytes(shape_str: str) -> int:
    """Total bytes of all array shapes in an HLO type string."""
    total = 0
    for dtype, dims in _SHAPE_RE.findall(shape_str):
        if dtype not in _DTYPE_BYTES:
            continue
        n = 1
        if dims:
            for d in dims.split(","):
                if d:
                    n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def parse_collectives(hlo_text: str) -> dict[str, dict[str, float]]:
    """Sum operand bytes of every collective in post-SPMD HLO."""
    out: dict[str, dict[str, float]] = {
        k: {"count": 0, "bytes": 0} for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        line = line.strip()
        m = re.match(r".*= (\([^)]*\)|\S+) (all-gather|all-reduce|"
                     r"reduce-scatter|all-to-all|collective-permute)", line)
        if not m:
            continue
        kind = m.group(2)
        # bytes: use the RESULT shape (what lands on the wire, roughly)
        out[kind]["count"] += 1
        out[kind]["bytes"] += _shape_bytes(m.group(1))
    return out


def roofline(flops: float, hbm_bytes: float,
             coll: dict[str, dict[str, float]]) -> dict[str, float]:
    """Three-term per-device roofline (seconds) on a MODELED_KIND chip."""
    pk = peaks(MODELED_KIND)
    compute_s = flops / pk["flops_bf16"]
    memory_s = hbm_bytes / pk["hbm_bw"]
    coll_bytes = sum(v["bytes"] * _ALGO_FACTOR[k] for k, v in coll.items())
    collective_s = coll_bytes / pk["ici_bw_per_link"]
    dominant = max(
        ("compute", compute_s), ("memory", memory_s),
        ("collective", collective_s), key=lambda kv: kv[1])[0]
    return {
        "compute_s": compute_s, "memory_s": memory_s,
        "collective_s": collective_s, "collective_bytes": coll_bytes,
        "dominant": dominant,
        "step_s_lower_bound": max(compute_s, memory_s, collective_s),
    }


def model_flops(cfg, shape) -> float:
    """6·N_active·D reference FLOPs for the whole step (train) or
    2·N_active·B for one decode token."""
    n = cfg.active_param_count()
    if shape.kind == "train":
        return 6.0 * n * shape.tokens
    if shape.kind == "prefill":
        return 2.0 * n * shape.tokens
    return 2.0 * n * shape.global_batch      # decode: one token per seq


def _moe_groups(shape) -> int:
    return max(32, shape.tokens // 2048)


def lower_train_cell(cfg, shape, mesh, n_micro: int = 1
                     ) -> tuple[jax.stages.Lowered, object]:
    opt = AdamW(schedule=cosine_schedule(3e-4, 2000, 100_000))
    dp = shd._dp_entry(mesh)
    step_cfg = TrainStepConfig(
        n_micro=n_micro,
        moe_groups=_moe_groups(shape),
        seq_spec=(NamedSharding(mesh, P(dp, "model", None))
                  if cfg.seq_shard else None))
    train_step = make_train_step(cfg, opt, step_cfg)

    state = abstract_train_state(cfg, opt)
    batch = input_specs(cfg, shape)
    state_sh = shd.state_shardings(state, mesh)
    batch_sh = shd.batch_shardings(batch, mesh)

    metrics = jax.eval_shape(train_step, state, batch)[1]
    metrics_sh = jax.tree.map(lambda _: NamedSharding(mesh, P()), metrics)

    jitted = jax.jit(
        train_step,
        in_shardings=(state_sh, batch_sh),
        out_shardings=(state_sh, metrics_sh),
        donate_argnums=(0,))
    with jax.set_mesh(mesh):
        lowered = jitted.lower(state, batch)
        compiled = lowered.compile()
    return lowered, compiled


def lower_prefill_cell(cfg, shape, mesh):
    """Prefill = forward pass only (logits for the full prompt)."""
    from repro.models.transformer import forward, init_params

    infer_cfg = dataclasses.replace(cfg, remat="none")
    dp = shd._dp_entry(mesh)
    seq_spec = (NamedSharding(mesh, P(dp, "model", None))
                if cfg.seq_shard else None)
    moe_groups = _moe_groups(shape)

    def prefill(params, batch):
        logits, _ = forward(infer_cfg, params, batch, moe_groups, seq_spec)
        return logits

    params = jax.eval_shape(lambda k: init_params(infer_cfg, k),
                            jax.random.PRNGKey(0))
    batch = {k: v for k, v in input_specs(cfg, shape).items()
             if k != "labels"}
    params_sh = shd.params_shardings(params, mesh)
    batch_sh = shd.batch_shardings(batch, mesh)
    out_abs = jax.eval_shape(prefill, params, batch)
    out_sh = NamedSharding(
        mesh, shd.fit_spec(P(dp, None, "model"), out_abs.shape, mesh))
    jitted = jax.jit(prefill, in_shardings=(params_sh, batch_sh),
                     out_shardings=out_sh)
    with jax.set_mesh(mesh):
        lowered = jitted.lower(params, batch)
        compiled = lowered.compile()
    return lowered, compiled


def lower_decode_cell(cfg, shape, mesh):
    serve_step = make_serve_step(cfg)
    from repro.models.transformer import init_params

    params = jax.eval_shape(lambda k: init_params(cfg, k),
                            jax.random.PRNGKey(0))
    cache = cache_specs(cfg, shape)
    token = input_specs(cfg, shape)["token"]
    dp = shd._dp_entry(mesh)

    params_sh = shd.params_shardings(params, mesh)
    cache_sh = shd.cache_shardings(cache, mesh)
    token_sh = NamedSharding(
        mesh, shd.fit_spec(P(dp, None), token.shape, mesh))
    logits_sh = NamedSharding(
        mesh, shd.fit_spec(P(dp, "model"),
                           (shape.global_batch, cfg.vocab_size), mesh))

    jitted = jax.jit(
        serve_step,
        in_shardings=(params_sh, cache_sh, token_sh),
        out_shardings=(logits_sh, cache_sh),
        donate_argnums=(1,))
    with jax.set_mesh(mesh):
        lowered = jitted.lower(params, cache, token)
        compiled = lowered.compile()
    return lowered, compiled


def _lower_fn(kind: str):
    return {"train": lower_train_cell, "prefill": lower_prefill_cell,
            "decode": lower_decode_cell}[kind]


def _compiled_costs(compiled) -> dict:
    cost = compiled.cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0]
    coll = parse_collectives(compiled.as_text())
    return {"flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            "coll": coll}


def _variant(cfg, layer_types: tuple):
    return dataclasses.replace(
        cfg, n_layers=len(layer_types), layer_types=tuple(layer_types))


def corrected_costs(cfg, shape, mesh) -> dict:
    """Layer-exact costs.

    XLA's cost analysis counts while-loop (scan) bodies ONCE, so the
    scan-over-layers program underreports flops/bytes/collectives by the
    trip count.  We recover exact totals linearly: lower a 0-layer
    variant (embeddings + loss/head) and a 1-layer variant per layer
    kind, then total = base + Σ_kind n_kind · (kind − base).  Memory
    analysis still comes from the full scan-based program (that is what
    deploys)."""
    lower = _lower_fn(shape.kind)

    def costs_of(variant_cfg):
        # minis use unchunked CE and unchunked attention: those lax.map/
        # scan bodies would be trip-count-undercounted; the dense forms
        # count identically and exactly
        _, compiled = lower(
            dataclasses.replace(variant_cfg, loss_chunk=0, attn_q_chunk=0),
            shape, mesh)
        return _compiled_costs(compiled)

    base = costs_of(_variant(cfg, ()))
    kinds: dict[str, int] = {}
    for k in cfg.layer_types:
        kinds[k] = kinds.get(k, 0) + 1

    total = {"flops": base["flops"], "bytes": base["bytes"],
             "coll": json.loads(json.dumps(base["coll"]))}
    per_kind = {}
    for kind, n in sorted(kinds.items()):
        one = costs_of(_variant(cfg, (kind,)))
        d_flops = one["flops"] - base["flops"]
        d_bytes = one["bytes"] - base["bytes"]
        per_kind[kind] = {"n_layers": n, "flops": d_flops, "bytes": d_bytes}
        total["flops"] += n * d_flops
        total["bytes"] += n * d_bytes
        for cname in _COLLECTIVES:
            dc = one["coll"][cname]["count"] - base["coll"][cname]["count"]
            db = one["coll"][cname]["bytes"] - base["coll"][cname]["bytes"]
            total["coll"][cname]["count"] += n * dc
            total["coll"][cname]["bytes"] += n * db
    total["per_kind"] = per_kind
    return total


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             outdir: Path | None = None, loss_chunk: int = 1024,
             overrides: dict | None = None,
             mesh_shape: tuple | None = None) -> dict:
    """``mesh_shape`` re-maps the SAME chips to a different logical
    (data, model) or (pod, data, model) split — the §Perf sharding lever
    (e.g. (64, 4): TP=4 instead of 16 on one 256-chip pod)."""
    opts = dict(loss_chunk=loss_chunk, vocab_pad=256,
                param_dtype="bfloat16", attn_q_chunk=1024, seq_shard=True)
    opts.update(overrides or {})
    cfg = dataclasses.replace(get(arch), **opts)
    shape = SHAPES[shape_name]
    ok, reason = cell_applicable(cfg, shape)
    if mesh_shape is not None:
        mesh_name = "x".join(map(str, mesh_shape))
    else:
        mesh_name = "2x16x16" if multi_pod else "16x16"
    record: dict = {
        "arch": arch, "shape": shape_name, "mesh": mesh_name,
        "device_kind": MODELED_KIND, "applicable": ok,
    }
    if not ok:
        record["skip_reason"] = reason
        return record

    if mesh_shape is not None:
        axes = (("pod", "data", "model") if len(mesh_shape) == 3
                else ("data", "model"))
        mesh = auto_mesh(mesh_shape, axes)
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh_chips(mesh)
    # NOTE: gradient accumulation (n_micro>1) currently triggers GSPMD
    # "involuntary full rematerialization" on the microbatch reshape
    # (XLA b/433785288); >HBM cells are documented in EXPERIMENTS.md with
    # the production mitigation (Pallas flash kernels on real TPU).
    t0 = time.time()
    lowered, compiled = _lower_fn(shape.kind)(cfg, shape, mesh)
    compile_s = time.time() - t0

    mem = compiled.memory_analysis()
    raw = _compiled_costs(compiled)
    corr = corrected_costs(cfg, shape, mesh)

    flops = corr["flops"]
    hbm_bytes = corr["bytes"]
    rl = roofline(flops, hbm_bytes, corr["coll"])
    mflops = model_flops(cfg, shape)
    record.update({
        "chips": chips,
        "compile_seconds": compile_s,
        "memory": {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "peak_bytes": (getattr(mem, "temp_size_in_bytes", 0) or 0)
            + (getattr(mem, "argument_size_in_bytes", 0) or 0),
        },
        "raw_scan_counted": raw,
        "per_kind": corr["per_kind"],
        "hlo_flops_per_device": flops,
        "hlo_bytes_per_device": hbm_bytes,
        "collectives": corr["coll"],
        "roofline": rl,
        "model_flops_total": mflops,
        "model_flops_per_device": mflops / chips,
        "useful_flops_ratio": (mflops / chips) / flops if flops else None,
    })
    if outdir:
        outdir.mkdir(parents=True, exist_ok=True)
        fname = f"{arch.replace('.', '_')}__{shape_name}__{mesh_name}.json"
        (outdir / fname).write_text(json.dumps(record, indent=1, default=str))
    return record


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default="all")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["16x16", "2x16x16",
                                                       "both"])
    ap.add_argument("--out", default="experiments/dryrun")
    args = ap.parse_args()

    archs = all_archs() if args.arch == "all" else [args.arch]
    shapes = list(SHAPES) if args.shape == "all" else [args.shape]
    meshes = {"16x16": [False], "2x16x16": [True],
              "both": [False, True]}[args.mesh]
    outdir = Path(args.out)

    failures = 0
    for arch in archs:
        for shape_name in shapes:
            for multi_pod in meshes:
                try:
                    rec = run_cell(arch, shape_name, multi_pod, outdir)
                except Exception as e:  # noqa: BLE001
                    failures += 1
                    print(f"FAIL {arch} {shape_name} "
                          f"{'2x16x16' if multi_pod else '16x16'}: "
                          f"{type(e).__name__}: {e}")
                    continue
                if not rec.get("applicable", True):
                    print(f"SKIP {arch} {shape_name}: {rec['skip_reason']}")
                    continue
                rl = rec["roofline"]
                print(f"OK   {arch:18s} {shape_name:12s} {rec['mesh']:8s} "
                      f"compile={rec['compile_seconds']:6.1f}s "
                      f"flops/dev={rec['hlo_flops_per_device']:.3e} "
                      f"dom={rl['dominant']:10s} "
                      f"peakMB={rec['memory']['peak_bytes']/1e6:9.1f}")
    if failures:
        raise SystemExit(f"{failures} cells failed")


if __name__ == "__main__":
    main()
