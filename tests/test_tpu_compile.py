"""The Pallas kernels compile for a TPU v5e at published widths.

Each raw kernel is compiled with ``interpret=False`` for a described
``v5e:2x2`` chip: the TPU compiler that ships with jax refuses here what
the chip would refuse, without a chip. Nothing runs, so results and
times are not checked. The topology is described inside a fixture (only
one process at a time may load the TPU library), and the compiles run
in the test's own process with the persistent compile cache off.
"""
import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.flash_attention import flash_attention
from repro.kernels.moe_gmm import grouped_matmul
from repro.kernels.ssd_scan import ssd_scan

BF16, F32, I32 = jnp.bfloat16, jnp.float32, jnp.int32


def _flash_grads(q, k, v):
    """(dq, dk, dv) through the kernel's own backward."""
    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=True, window=4096,
                              interpret=False)
        return jnp.sum(out.astype(F32))
    return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)


#: h2o-danube-1.8b's train cell: batch 4 x 2048, 32 query / 8 kv heads x 80
DANUBE_TRAIN = [((4, 2048, 32, 80), BF16), ((4, 2048, 8, 80), BF16),
                ((4, 2048, 8, 80), BF16)]

#: kernel -> (call, argument shapes, kernel names the compiled program
#: holds) at a model's published widths
CASES = {
    # h2o-danube-1.8b: 32 query / 8 kv heads x 80, window 4096
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=4096,
                                        interpret=False),
        [((1, 4096, 32, 80), BF16), ((1, 4096, 8, 80), BF16),
         ((1, 4096, 8, 80), BF16)], ("flash_fwd",)),
    # its backward at the train cell's widths: forward, dq and dk/dv
    "flash_attention_grad": (
        _flash_grads, DANUBE_TRAIN, ("flash_fwd", "flash_dq", "flash_dkv")),
    # mamba2-780m: 48 heads x 64, state 128, chunk 256
    "ssd_scan": (
        lambda x, a, b, c: ssd_scan(x, a, b, c, chunk=256, interpret=False),
        [((1, 4096, 48, 64), BF16), ((1, 4096, 48), F32),
         ((1, 4096, 1, 128), BF16), ((1, 4096, 1, 128), BF16)], ()),
    # olmoe-1b-7b: 4096 tokens x top-8, d 2048, 64 experts x 1024
    "grouped_matmul": (
        lambda x, w, g: grouped_matmul(x, w, g, interpret=False),
        [((4096 * 8, 2048), BF16), ((64, 2048, 1024), BF16),
         ((64,), I32)], ()),
}


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        try:
            return topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 - any failure means "cannot"
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache as cc
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        yield
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        cc.reset_cache()


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(name, one_chip, no_persistent_cache):
    fn, shapes, kernels = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    for kernel in kernels:
        assert kernel in text, kernel


def test_flash_compiles_for_data_parallel_v5e(topo, no_persistent_cache,
                                              monkeypatch):
    """On a (data=4, model=1) mesh the attention sub-layer's kernel runs
    under ``shard_map``, forward and backward: the compiler does not
    partition a Mosaic kernel itself."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.kernels import ops
    from repro.models.attention import _flash
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(4, 1), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    batch = NamedSharding(mesh, P("data"))
    args = [jax.ShapeDtypeStruct(s, d, sharding=batch)
            for s, d in DANUBE_TRAIN]

    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v, 4096).astype(F32))

    with jax.set_mesh(mesh):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *args).compile().as_text()
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert kernel in text, kernel


def test_flash_compiles_for_head_sharded_v5e(topo, no_persistent_cache,
                                             monkeypatch):
    """On a (data=1, model=4) mesh, danube's 32/8 heads lie 8/2 on each
    chip: the kernel runs under ``shard_map`` over the heads, forward
    and backward, and neither pass gathers the heads."""
    import numpy as np
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from repro.kernels import ops
    from repro.models.attention import _flash
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    mesh = Mesh(np.array(topo.devices).reshape(1, 4), ("data", "model"),
                axis_types=(jax.sharding.AxisType.Auto,) * 2)
    heads = NamedSharding(mesh, P("data", None, "model", None))
    args = [jax.ShapeDtypeStruct(s, d, sharding=heads)
            for s, d in DANUBE_TRAIN]

    def loss(q, k, v):
        return jnp.sum(_flash(q, k, v, 4096).astype(F32))

    with jax.set_mesh(mesh):
        text = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
            *args).compile().as_text()
    for kernel in ("flash_fwd", "flash_dq", "flash_dkv"):
        assert kernel in text, kernel
    assert "all-gather" not in text
