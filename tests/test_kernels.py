"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention as fa_kernel
from repro.kernels.ssd_scan import ssd_scan as ssd_kernel
from repro.kernels.moe_gmm import grouped_matmul as gmm_kernel

KEY = jax.random.PRNGKey(7)


def rand(key, shape, dtype, scale=1.0):
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


TOLS = {jnp.float32: 2e-5, jnp.bfloat16: 2e-2}


class TestFlashAttention:
    @pytest.mark.parametrize("s,hq,hkv,d", [
        (128, 4, 4, 32),     # MHA
        (128, 4, 2, 32),     # GQA
        (256, 8, 1, 64),     # MQA
        (128, 2, 2, 128),    # big head_dim
    ])
    @pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
    def test_causal_sweep(self, s, hq, hkv, d, dtype):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (2, s, hq, d), dtype)
        k = rand(ks[1], (2, s, hkv, d), dtype)
        v = rand(ks[2], (2, s, hkv, d), dtype)
        out = fa_kernel(q, k, v, causal=True, block_q=64, block_k=64,
                        interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            atol=TOLS[dtype], rtol=TOLS[dtype])

    @pytest.mark.parametrize("window", [32, 64, 128])
    def test_sliding_window(self, window):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (1, 256, 4, 32), jnp.float32)
        k = rand(ks[1], (1, 256, 2, 32), jnp.float32)
        v = rand(ks[2], (1, 256, 2, 32), jnp.float32)
        out = fa_kernel(q, k, v, causal=True, window=window,
                        block_q=64, block_k=64, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_bidirectional(self):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (2, 128, 4, 32), jnp.float32)
        k = rand(ks[1], (2, 128, 4, 32), jnp.float32)
        v = rand(ks[2], (2, 128, 4, 32), jnp.float32)
        out = fa_kernel(q, k, v, causal=False, block_q=64, block_k=64,
                        interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_ops_wrapper_pads_ragged_seq(self):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (1, 100, 2, 32), jnp.float32)
        k = rand(ks[1], (1, 100, 2, 32), jnp.float32)
        v = rand(ks[2], (1, 100, 2, 32), jnp.float32)
        for causal in (True, False):
            out = ops.flash_attention(q, k, v, causal=causal,
                                      block_q=32, block_k=32)
            want = ref.flash_attention_ref(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                       atol=2e-5, rtol=2e-5)


class TestSSDScan:
    @pytest.mark.parametrize("s,h,p,g,n,chunk", [
        (64, 2, 16, 1, 16, 16),
        (128, 4, 32, 2, 16, 32),
        (128, 4, 32, 4, 8, 64),
    ])
    def test_sweep_vs_sequential(self, s, h, p, g, n, chunk):
        ks = jax.random.split(KEY, 4)
        x = rand(ks[0], (2, s, h, p), jnp.float32, 0.5)
        log_a = -jax.nn.softplus(
            jax.random.normal(ks[1], (2, s, h))) * 0.3
        b = rand(ks[2], (2, s, g, n), jnp.float32, 0.3)
        c = rand(ks[3], (2, s, g, n), jnp.float32, 0.3)
        y, hf = ssd_kernel(x, log_a, b, c, chunk=chunk, interpret=True)
        y_ref, h_ref = ref.ssd_scan_ref(x, log_a, b, c)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(hf), np.asarray(h_ref),
                                   atol=1e-4, rtol=1e-4)

    def test_initial_state(self):
        ks = jax.random.split(KEY, 5)
        x = rand(ks[0], (1, 64, 2, 16), jnp.float32, 0.5)
        log_a = -jax.nn.softplus(jax.random.normal(ks[1], (1, 64, 2))) * 0.3
        b = rand(ks[2], (1, 64, 1, 16), jnp.float32, 0.3)
        c = rand(ks[3], (1, 64, 1, 16), jnp.float32, 0.3)
        h0 = rand(ks[4], (1, 2, 16, 16), jnp.float32, 0.2)
        y, hf = ssd_kernel(x, log_a, b, c, chunk=16, initial_state=h0,
                           interpret=True)
        y_ref, h_ref = ref.ssd_scan_ref(x, log_a, b, c, initial_state=h0)
        np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                                   atol=1e-4, rtol=1e-4)
        np.testing.assert_allclose(np.asarray(hf), np.asarray(h_ref),
                                   atol=1e-4, rtol=1e-4)

    def test_bf16_inputs(self):
        ks = jax.random.split(KEY, 4)
        x = rand(ks[0], (1, 64, 2, 16), jnp.bfloat16, 0.5)
        log_a = (-jax.nn.softplus(
            jax.random.normal(ks[1], (1, 64, 2))) * 0.3)
        b = rand(ks[2], (1, 64, 1, 16), jnp.bfloat16, 0.3)
        c = rand(ks[3], (1, 64, 1, 16), jnp.bfloat16, 0.3)
        y, _ = ssd_kernel(x, log_a, b, c, chunk=16, interpret=True)
        y_ref, _ = ref.ssd_scan_ref(x, log_a, b, c)
        np.testing.assert_allclose(
            np.asarray(y, np.float32), np.asarray(y_ref, np.float32),
            atol=5e-2, rtol=5e-2)


class TestGroupedMatmul:
    @pytest.mark.parametrize("t,d,e,f,br,bc", [
        (64, 32, 4, 64, 16, 16),
        (128, 64, 8, 128, 32, 64),
        (96, 64, 5, 96, 16, 32),
    ])
    def test_sweep(self, t, d, e, f, br, bc):
        ks = jax.random.split(KEY, 3)
        x = rand(ks[0], (t, d), jnp.float32)
        w = rand(ks[1], (e, d, f), jnp.float32, 0.1)
        # random group sizes summing to t
        cuts = np.sort(np.random.RandomState(0).randint(0, t, e - 1))
        gs = jnp.asarray(np.diff(np.concatenate([[0], cuts, [t]])),
                         jnp.int32)
        out = gmm_kernel(x, w, gs, block_rows=br, block_cols=bc,
                         interpret=True)
        want = ref.grouped_matmul_ref(x, w, gs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_empty_groups(self):
        ks = jax.random.split(KEY, 2)
        x = rand(ks[0], (32, 16), jnp.float32)
        w = rand(ks[1], (4, 16, 32), jnp.float32, 0.1)
        gs = jnp.array([0, 32, 0, 0], jnp.int32)
        out = gmm_kernel(x, w, gs, block_rows=8, block_cols=16,
                         interpret=True)
        want = ref.grouped_matmul_ref(x, w, gs)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_bf16(self):
        ks = jax.random.split(KEY, 2)
        x = rand(ks[0], (64, 32), jnp.bfloat16)
        w = rand(ks[1], (4, 32, 32), jnp.bfloat16, 0.1)
        gs = jnp.array([16, 16, 16, 16], jnp.int32)
        out = gmm_kernel(x, w, gs, block_rows=16, block_cols=16,
                         interpret=True)
        want = ref.grouped_matmul_ref(x, w, gs)
        np.testing.assert_allclose(
            np.asarray(out, np.float32), np.asarray(want, np.float32),
            atol=2e-2, rtol=2e-2)


class TestMoEDispatchEquivalence:
    def test_einsum_vs_ragged_moe(self):
        """The two dispatch strategies agree when nothing is dropped."""
        import dataclasses
        from repro.configs import get_smoke
        from repro.models import Model, synthetic_batch
        cfg_e = dataclasses.replace(get_smoke("olmoe-1b-7b"),
                                    capacity_factor=8.0)  # no drops
        cfg_r = dataclasses.replace(cfg_e, moe_dispatch="ragged")
        m_e, m_r = Model(cfg_e), Model(cfg_r)
        params = m_e.init(KEY)
        batch = synthetic_batch(cfg_e, 2, 32, KEY)
        le, _ = jax.jit(lambda p, b: m_e.loss(p, b))(params, batch)
        lr_, _ = jax.jit(lambda p, b: m_r.loss(p, b))(params, batch)
        assert abs(float(le) - float(lr_)) < 5e-3


class TestInterpretDefault:
    @pytest.mark.parametrize("backend,want", [
        ("cpu", True), ("tpu", False), ("gpu", False)])
    def test_interpret_only_on_cpu(self, backend, want, monkeypatch):
        """Only the CPU interprets; any other backend compiles the real
        kernels, so a device that cannot run them fails loudly."""
        monkeypatch.setattr(jax, "default_backend", lambda: backend)
        assert ops._interpret_default() is want
