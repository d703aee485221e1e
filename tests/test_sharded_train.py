"""Training on a head-sharded (data=1, model=4) mesh, and the four-chip
cell's yardstick.

On four virtual CPU devices (``--xla_force_host_platform_device_count``,
in a child process, since the device count is fixed when JAX starts):
the flash kernel under ``shard_map`` with the heads over ``model``
against the XLA attention, forward and gradients; and the program's
train step on that mesh against the benchmark's plain float32
reference spread over the same four devices. In the parent: the cell's
FLOPs per token, the reference's layout, and the two collective readers on a
hand-made reduced trace.
"""
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from benchmarks.chip import flops, harness  # noqa: E402
from benchmarks.chip.paths import train_sharded  # noqa: E402

#: the kernel against XLA attention, both float32 on the CPU: the same
#: sums in another order (the ``test_kernels.py`` grad-parity bound)
KERNEL_TOL = 5e-5
#: the program's step against the reference, both float32: sums in
#: another order over three steps (``test_bench_reference.py``)
TIGHT = 1e-5

#: danube's shape in small: 4 query heads per KV head, whole GQA groups
#: per device over 4 (16/4 heads), full causal attention over one
#: kernel block (seq 128)
DANUBE_TP = {"n_layers": 2, "d_model": 64, "n_heads": 16, "n_kv_heads": 4,
             "head_dim": 8, "d_ff": 128, "vocab_size": 256,
             "layer_types": ["swa", "swa"], "window": 4096,
             "mlp_act": "silu", "tie_embeddings": False,
             "rope_theta": 10000.0, "norm_eps": 1e-6,
             "param_dtype": "float32", "compute_dtype": "float32"}


def steer_to_tpu():
    """Make the program take its TPU attention path, the kernel
    interpreted (as ``test_kernels.py`` does it)."""
    import jax
    from repro.kernels import ops
    jax.default_backend = lambda: "tpu"
    ops._interpret_default = lambda: True


def child_flash() -> dict:
    """Worst relative gap of the head-sharded kernel's output and
    (dq, dk, dv) against the XLA attention, on a (1, 4) mesh."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_local_mesh
    from repro.models.attention import _flash, full_attention
    steer_to_tpu()
    mesh = make_local_mesh(model=4)
    ks = jax.random.split(jax.random.PRNGKey(3), 4)
    b, s, hq, hkv, d = 2, 128, 16, 4, 32
    shapes = [(b, s, hq, d), (b, s, hkv, d), (b, s, hkv, d), (b, s, hq, d)]
    heads = NamedSharding(mesh, P("data", None, "model", None))
    q, k, v, ct = (jax.device_put(jax.random.normal(key, sh, jnp.float32),
                                  heads) for key, sh in zip(ks, shapes))

    def out_and_grads(fn):
        out, vjp = jax.vjp(fn, q, k, v)
        return (out, *vjp(ct))

    with jax.set_mesh(mesh):
        got = jax.jit(lambda: out_and_grads(
            lambda q, k, v: _flash(q, k, v, 0)))()
        text = jax.jit(lambda q, k, v: _flash(q, k, v, 0)).lower(
            q, k, v).as_text()
    want = jax.jit(lambda: out_and_grads(
        lambda q, k, v: full_attention(q, k, v, causal=True)))()
    gaps = {name: float(np.max(np.abs(np.asarray(g) - np.asarray(w)))
                        / np.max(np.abs(np.asarray(w))))
            for name, g, w in zip(("out", "dq", "dk", "dv"), got, want)}
    return {"gaps": gaps, "shard_map": "shard_map" in text
            or "sdy.manual_computation" in text}


def child_train() -> dict:
    """The cell's path at a small size on four devices: the program's
    step on a (1, 4) mesh, the reference spread over the same devices."""
    import jax
    from benchmarks.chip import compare
    from repro.core.telemetry import tallies
    steer_to_tpu()
    spec = {"config": {"arch": "h2o-danube-1.8b", "model": DANUBE_TP,
                       "mesh": {"data": 1, "model": 4}},
            "traffic": {**harness.load("traffic", "train-sharded-4x2048"),
                        "batch": 2, "seq": 128},
            "seed": 2**33 + 11, "seconds": 0.1, "trace": False,
            "devices": jax.devices()[:4], "t0": time.perf_counter()}
    before = tallies().get("papas.attn.kernel", 0)
    trainer = train_sharded.Trainer(spec)
    program = trainer.checked_steps()
    abstract = trainer.abstract_params
    trainer.free()
    kernel = tallies().get("papas.attn.kernel", 0) - before
    ref = train_sharded.reference(spec, abstract)
    shards = {str(sh.spec) for sh in jax.tree.leaves(
        train_sharded.layout(abstract, spec["devices"]))}
    return {"readings": compare.train_readings(program, ref),
            "grad_norm": [program["grad_norm"], ref["grad_norm"]],
            "kernel_tallies": kernel, "layouts": sorted(shards)}


@pytest.fixture(scope="module")
def four_devices():
    """The children's results, one process for all of them."""
    env = {**os.environ, "JAX_PLATFORMS": "cpu",
           "XLA_FLAGS": "--xla_force_host_platform_device_count=4",
           "PYTHONPATH": os.pathsep.join(
               [str(ROOT / "src"), str(ROOT),
                os.environ.get("PYTHONPATH", "")])}
    r = subprocess.run([sys.executable, __file__], env=env, cwd=str(ROOT),
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_head_sharded_kernel_matches_xla(four_devices):
    got = four_devices["flash"]
    assert got["shard_map"]
    for name, gap in got["gaps"].items():
        assert gap < KERNEL_TOL, (name, gap)


def test_sharded_step_matches_sharded_reference(four_devices):
    """The step as the chip runs it (the head-sharded kernel,
    interpreted) against the reference over the same four devices."""
    got = four_devices["train"]
    assert max(got["readings"].values()) < TIGHT, got["readings"]
    assert got["grad_norm"][0] == pytest.approx(got["grad_norm"][1],
                                                rel=TIGHT)
    assert got["kernel_tallies"] > 0     # the step took the kernel
    # the reference's leaves lie over the four devices, not on one
    assert any("chips" in spec for spec in got["layouts"])


def test_cell_flops_per_token():
    m = harness.load("configs", "h2o-danube-1.8b-tp4")["model"]
    # 24 x 69.5 M (attention 16.4 M + SwiGLU 53.1 M) + 81.9 M head;
    # attention at a mean causal context of 1024.5 adds 0.755 GFLOP
    assert flops.matmul_params(m) == 1_749_155_840
    assert flops.train_flops_per_token(m, 2048) == pytest.approx(
        11_250_278_400.0)


@pytest.mark.parametrize("shape,n,want", [
    ((24, 2560, 6912), 4, 2),       # SwiGLU in: the hidden axis
    ((24, 2560, 2560), 4, 1),       # a tie: the first
    ((32000, 2560), 4, 0),          # the embedding: the vocabulary
    ((24, 2560), 4, 1),             # stacked norm gains
    ((3, 5), 4, None),              # no axis divides: whole on each
])
def test_reference_layout_axis(shape, n, want):
    assert train_sharded.leaf_axis(shape, n) == want


#: a chip's collectives take 0.5 s of a 2 s window, 0.1 s of it exposed
TWO_S = {"window_s": 2.0, "collective_s": 0.5, "collective_exposed_s": 0.1}


@pytest.mark.parametrize("metric,found,want", [
    ("collective_share.train", TWO_S, 25.0),
    ("collective_share.train",
     {**TWO_S, "collective_s": 0.0, "collective_exposed_s": 0.0}, 0.0),
    ("collective_share.train", None, None),
    ("collective_exposed_share.train", TWO_S, 5.0),
    ("collective_exposed_share.train",
     {**TWO_S, "collective_exposed_s": 0.0}, 0.0),
    ("collective_exposed_share.train",
     {**TWO_S, "window_s": 0.0}, None),
    ("collective_exposed_share.train", None, None),
], ids=["four-chips", "no-collective", "no-trace", "exposed-part",
        "all-overlapped", "empty-window", "exposed-no-trace"])
def test_collective_share_reader(metric, found, want):
    from benchmarks.chip import run
    trace = None if found is None else {
        "chips": 4, "busy_s": 1.9, "idle_share": 0.05, **found}
    read = run.reader(metric)
    assert read({"trace": trace}) == (None if want is None
                                      else pytest.approx(want))
    assert read({}) is None

if __name__ == "__main__":
    import jax
    assert len(jax.devices()) == 4, jax.devices()
    print(json.dumps({"flash": child_flash(), "train": child_train()}))
