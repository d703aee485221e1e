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

#: kernel -> (call, argument shapes) at a model's published widths
CASES = {
    # h2o-danube-1.8b: 32 query / 8 kv heads x 80, window 4096
    "flash_attention": (
        lambda q, k, v: flash_attention(q, k, v, causal=True, window=4096,
                                        interpret=False),
        [((1, 4096, 32, 80), BF16), ((1, 4096, 8, 80), BF16),
         ((1, 4096, 8, 80), BF16)]),
    # mamba2-780m: 48 heads x 64, state 128, chunk 256
    "ssd_scan": (
        lambda x, a, b, c: ssd_scan(x, a, b, c, chunk=256, interpret=False),
        [((1, 4096, 48, 64), BF16), ((1, 4096, 48), F32),
         ((1, 4096, 1, 128), BF16), ((1, 4096, 1, 128), BF16)]),
    # olmoe-1b-7b: 4096 tokens x top-8, d 2048, 64 experts x 1024
    "grouped_matmul": (
        lambda x, w, g: grouped_matmul(x, w, g, interpret=False),
        [((4096 * 8, 2048), BF16), ((64, 2048, 1024), BF16),
         ((64,), I32)]),
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
    fn, shapes = CASES[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
