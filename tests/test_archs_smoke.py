"""Per-architecture smoke tests: reduced config, one train step on CPU,
shape + finiteness assertions; decode step where applicable."""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import ALIASES, all_archs, get, get_smoke
from repro.models import Model, synthetic_batch
from repro.optim.adamw import AdamW, cosine_schedule
from repro.train.step import init_train_state, make_train_step

KEY = jax.random.PRNGKey(0)


@pytest.fixture(scope="module", params=all_archs())
def arch(request):
    return request.param


class TestSmoke:
    def test_forward_shapes_and_finite(self, arch):
        cfg = get_smoke(arch)
        m = Model(cfg)
        params = m.init(KEY)
        batch = synthetic_batch(cfg, batch=2, seq=32, key=KEY)
        logits, aux = jax.jit(lambda p, b: m.forward(p, b))(params, batch)
        assert logits.shape == (2, 32, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all())
        for v in aux.values():
            assert bool(jnp.isfinite(v))

    def test_one_train_step(self, arch):
        cfg = get_smoke(arch)
        opt = AdamW(schedule=cosine_schedule(1e-3, 10, 100))
        state = init_train_state(cfg, opt, KEY)
        step = jax.jit(make_train_step(cfg, opt))
        batch = synthetic_batch(cfg, batch=2, seq=32, key=KEY)
        new_state, metrics = step(state, batch)
        assert bool(jnp.isfinite(metrics["loss"]))
        assert bool(jnp.isfinite(metrics["grad_norm"]))
        assert int(new_state["step"]) == 1
        # parameters actually moved
        delta = jax.tree.map(
            lambda a, b: float(jnp.abs(a.astype(jnp.float32)
                                       - b.astype(jnp.float32)).max()),
            state["params"], new_state["params"])
        assert max(jax.tree.leaves(delta)) > 0

    def test_decode_step_if_applicable(self, arch):
        cfg = get_smoke(arch)
        if not cfg.has_decode():
            pytest.skip("encoder-only")
        m = Model(cfg)
        params = m.init(KEY)
        cache = m.init_cache(batch=2, max_len=16)
        tok = jnp.zeros((2, 1), jnp.int32)
        step = jax.jit(m.decode_step)
        logits, cache = step(params, cache, tok)
        assert logits.shape == (2, cfg.vocab_size)
        assert bool(jnp.isfinite(logits).all())
        assert int(cache["pos"]) == 1
        logits2, cache = step(params, cache, tok)
        assert int(cache["pos"]) == 2

    def test_prefill_decode_consistency(self, arch):
        """Greedy decode after teacher-forcing matches forward logits."""
        cfg = get_smoke(arch)
        if not cfg.has_decode() or cfg.input_mode != "tokens":
            pytest.skip("needs token-mode causal LM")
        m = Model(cfg)
        params = m.init(KEY)
        toks = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
        logits_all, _ = m.forward(params, {"tokens": toks})
        cache = m.init_cache(batch=1, max_len=16, dtype=jnp.float32)
        outs = []
        for t in range(8):
            lg, cache = m.decode_step(params, cache, toks[:, t:t + 1])
            outs.append(lg)
        dec = jnp.stack(outs, axis=1)
        err = jnp.abs(dec - logits_all).max()
        assert float(err) < 0.1, f"decode/prefill mismatch {float(err)}"


class TestConfigsExact:
    """The full configs carry the exact published hyperparameters."""

    EXPECT = {
        "internvl2-26b": dict(n_layers=48, d_model=6144, n_heads=48,
                              n_kv_heads=8, d_ff=16384, vocab_size=92553),
        "gemma-7b": dict(n_layers=28, d_model=3072, n_heads=16,
                         n_kv_heads=16, d_ff=24576, vocab_size=256000,
                         head_dim=256),
        "h2o-danube-1.8b": dict(n_layers=24, d_model=2560, n_heads=32,
                                n_kv_heads=8, d_ff=6912, vocab_size=32000),
        "deepseek-7b": dict(n_layers=30, d_model=4096, n_heads=32,
                            n_kv_heads=32, d_ff=11008, vocab_size=102400),
        "gemma3-1b": dict(n_layers=26, d_model=1152, n_heads=4,
                          n_kv_heads=1, d_ff=6912, vocab_size=262144),
        "hubert-xlarge": dict(n_layers=48, d_model=1280, n_heads=16,
                              n_kv_heads=16, d_ff=5120, vocab_size=504),
        "qwen2-moe-a2.7b": dict(n_layers=24, d_model=2048, n_heads=16,
                                n_kv_heads=16, vocab_size=151936,
                                n_experts=60, top_k=4, moe_d_ff=1408),
        "olmoe-1b-7b": dict(n_layers=16, d_model=2048, n_heads=16,
                            n_kv_heads=16, vocab_size=50304,
                            n_experts=64, top_k=8, moe_d_ff=1024),
        "mamba2-780m": dict(n_layers=48, d_model=1536, vocab_size=50280,
                            ssm_state=128),
        "hymba-1.5b": dict(n_layers=32, d_model=1600, n_heads=25,
                           n_kv_heads=5, d_ff=5504, vocab_size=32001,
                           ssm_state=16),
    }

    @pytest.mark.parametrize("arch", sorted(ALIASES))
    def test_exact_numbers(self, arch):
        cfg = get(arch)
        for field, want in self.EXPECT[arch].items():
            assert getattr(cfg, field) == want, (arch, field)

    def test_gemma3_pattern_five_to_one(self):
        lt = get("gemma3-1b").layer_types
        assert len(lt) == 26
        assert lt[5] == "attn" and lt[11] == "attn"
        assert lt.count("attn") == 4 and lt.count("swa") == 22

    def test_hymba_three_global(self):
        lt = get("hymba-1.5b").layer_types
        assert [i for i, k in enumerate(lt) if k == "hyb_g"] == [0, 15, 31]
