"""Per-kernel shape/dtype sweeps vs the ref.py oracles (interpret mode)."""
import functools

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
        out = fa_kernel(q, k, v, causal=True, block=64, interpret=True)
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
        out = fa_kernel(q, k, v, causal=True, window=window, block=64,
                        interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_bidirectional(self):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (2, 128, 4, 32), jnp.float32)
        k = rand(ks[1], (2, 128, 4, 32), jnp.float32)
        v = rand(ks[2], (2, 128, 4, 32), jnp.float32)
        out = fa_kernel(q, k, v, causal=False, block=64, interpret=True)
        want = ref.flash_attention_ref(q, k, v, causal=False)
        np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                   atol=2e-5, rtol=2e-5)

    def test_ops_wrapper_pads_ragged_seq(self):
        ks = jax.random.split(KEY, 3)
        q = rand(ks[0], (1, 100, 2, 32), jnp.float32)
        k = rand(ks[1], (1, 100, 2, 32), jnp.float32)
        v = rand(ks[2], (1, 100, 2, 32), jnp.float32)
        for causal in (True, False):
            out = ops.flash_attention(q, k, v, causal=causal)
            want = ref.flash_attention_ref(q, k, v, causal=causal)
            np.testing.assert_allclose(np.asarray(out), np.asarray(want),
                                       atol=2e-5, rtol=2e-5)

    @pytest.mark.parametrize("s,hq,hkv,d,window,block,remat", [
        (256, 4, 1, 80, 0, 128, False),       # GQA 4:1, head 80, causal
        (256, 2, 2, 64, 0, 128, False),       # MHA 1:1, head 64
        (256, 4, 1, 80, 4096, 128, False),    # window >= S: causal only
        (384, 4, 1, 64, 160, 128, False),     # window < S: tiles skipped
        (200, 4, 1, 80, 0, None, False),      # S padded to the block
        (256, 4, 1, 80, 0, 128, True),        # under jax.checkpoint
    ], ids=["gqa4-d80", "mha-d64", "window-ge-s", "window-lt-s", "padded",
            "checkpoint"])
    def test_grad_parity(self, s, hq, hkv, d, window, block, remat):
        """The kernel's output and its own backward's (dq, dk, dv) match
        ``jax.grad`` through the XLA attention (``full_attention``; the
        dense oracle where the window is shorter than S)."""
        from repro.models.attention import full_attention
        ks = jax.random.split(KEY, 4)
        q = rand(ks[0], (1, s, hq, d), jnp.float32)
        k = rand(ks[1], (1, s, hkv, d), jnp.float32)
        v = rand(ks[2], (1, s, hkv, d), jnp.float32)
        ct = rand(ks[3], (1, s, hq, d), jnp.float32)
        if block is None:            # the ops wrapper pads S to its block
            kernel = functools.partial(ops.flash_attention, causal=True,
                                       window=window)
        else:
            kernel = functools.partial(fa_kernel, causal=True, window=window,
                                       block=block, interpret=True)
        if remat:
            kernel = jax.checkpoint(kernel)
        if window and window < s:
            xla = functools.partial(ref.flash_attention_ref, causal=True,
                                    window=window)
        else:
            xla = functools.partial(full_attention, causal=True)

        def out_and_grads(fn):
            out, vjp = jax.vjp(fn, q, k, v)
            return (out, *vjp(ct))

        got = jax.jit(lambda: out_and_grads(kernel))()
        want = jax.jit(lambda: out_and_grads(xla))()
        for name, g, w in zip(("out", "dq", "dk", "dv"), got, want):
            np.testing.assert_allclose(np.asarray(g), np.asarray(w),
                                       atol=5e-5, rtol=5e-5, err_msg=name)


class TestFlashSelection:
    #: h2o-danube-1.8b's train step on one chip: 32 query / 8 kv heads
    TPU = dict(backend="tpu", cached=False, softcap=0.0, seq=2048,
               n_heads=32, n_kv_heads=8, model_axis=1)

    @pytest.mark.parametrize("change,want", [
        ({}, True),
        ({"seq": 128}, True),                # one block
        ({"backend": "cpu"}, False),
        ({"backend": "gpu"}, False),
        ({"cached": True}, False),           # decode
        ({"softcap": 50.0}, False),          # the kernel has no softcap
        ({"seq": 127}, False),               # shorter than a block
        ({"model_axis": 16}, False),         # kv heads split: 8 over 16
        ({"model_axis": 4}, True),           # whole GQA groups: 8/2 a chip
        ({"model_axis": 2}, True),
        ({"model_axis": 3}, False),          # 32 query heads over 3
        ({"n_heads": 12, "n_kv_heads": 4, "model_axis": 8}, False),
    ])
    def test_selected_only_where_it_applies(self, change, want):
        from repro.models.attention import flash_selected
        assert flash_selected(**{**self.TPU, **change}) is want

    @staticmethod
    def _danube_block(s, window=4096):
        """h2o-danube-1.8b's attention sub-layer at its widths, with the
        inputs, on a batch of one."""
        from repro.models.attention import attn_block
        ks = jax.random.split(KEY, 5)
        d, hq, hkv, hd = 2560, 32, 8, 80
        p = {"wq": rand(ks[0], (d, hq * hd), jnp.float32, 0.02),
             "wk": rand(ks[1], (d, hkv * hd), jnp.float32, 0.02),
             "wv": rand(ks[2], (d, hkv * hd), jnp.float32, 0.02),
             "wo": rand(ks[3], (hq * hd, d), jnp.float32, 0.02)}
        x = rand(ks[4], (1, s, d), jnp.float32)
        return jax.jit(lambda x, p: attn_block(
            x, p, n_heads=hq, n_kv_heads=hkv, head_dim=hd, kind="swa",
            window=window, positions=jnp.arange(s)[None],
            rope_theta=10_000.0)[0]), x, p

    def test_cpu_danube_block_takes_xla_path(self):
        from repro.core.telemetry import tallies
        fn, x, p = self._danube_block(256)
        before = tallies()
        jaxpr = str(jax.make_jaxpr(fn)(x, p))
        after = tallies()
        assert "pallas_call" not in jaxpr
        assert (after.get("papas.attn.xla", 0)
                - before.get("papas.attn.xla", 0)) == 1
        assert (after.get("papas.attn.kernel", 0)
                == before.get("papas.attn.kernel", 0))

    def test_tpu_selection_runs_kernel_in_block(self, monkeypatch):
        """Where the backend reads as a TPU, the danube-shaped sub-layer
        runs the kernel (interpreted here) and agrees with its XLA path."""
        from repro.core.telemetry import tallies
        fn, x, p = self._danube_block(256)
        want = fn(x, p)
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        monkeypatch.setattr(ops, "_interpret_default", lambda: True)
        fn, _, _ = self._danube_block(256)
        before = tallies().get("papas.attn.kernel", 0)
        got = fn(x, p)
        assert tallies().get("papas.attn.kernel", 0) == before + 1
        np.testing.assert_allclose(np.asarray(got, np.float32),
                                   np.asarray(want, np.float32),
                                   atol=2e-2, rtol=2e-2)


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
