"""End-to-end training integration: loss decreases; gang == serial."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.data.pipeline import SyntheticStream
from repro.optim.adamw import (
    AdamW, cosine_schedule, global_norm, linear_schedule,
)
from repro.train.step import init_train_state, make_train_step

KEY = jax.random.PRNGKey(11)


class TestTrainingLoop:
    def test_loss_decreases_on_learnable_data(self):
        """Tiny LM on a fixed repeating batch must overfit."""
        cfg = get_smoke("deepseek-7b")
        opt = AdamW(schedule=cosine_schedule(3e-3, 5, 60),
                    weight_decay=0.0)
        state = init_train_state(cfg, opt, KEY)
        step = jax.jit(make_train_step(cfg, opt))
        toks = jax.random.randint(KEY, (4, 32), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
        first = last = None
        for i in range(60):
            state, m = step(state, batch)
            if i == 0:
                first = float(m["loss"])
            last = float(m["loss"])
        assert last < first * 0.7, (first, last)

    def test_microbatching_matches_full_batch_grads(self):
        """n_micro=2 must give (numerically) the same step as n_micro=1."""
        cfg = get_smoke("gemma-7b")
        opt = AdamW(schedule=cosine_schedule(1e-3, 2, 10), clip_norm=0.0)
        state1 = init_train_state(cfg, opt, KEY)
        state2 = jax.tree.map(lambda x: x, state1)
        stream = SyntheticStream(cfg, global_batch=4, seq_len=16, seed=0)
        batch = {k: jnp.asarray(v) for k, v in stream.batch_at(0).items()}
        s1, m1 = jax.jit(make_train_step(cfg, opt))(state1, batch)
        s2, m2 = jax.jit(make_train_step(cfg, opt, n_micro=2))(state2, batch)
        # bf16 compute reassociates across the micro split: ~1% slack
        np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                                   rtol=1e-2)
        for a, b in zip(jax.tree.leaves(s1["params"]),
                        jax.tree.leaves(s2["params"])):
            np.testing.assert_allclose(
                np.asarray(a, np.float32), np.asarray(b, np.float32),
                atol=2e-2)


class TestOptim:
    def test_schedules(self):
        cos = cosine_schedule(1.0, 10, 100)
        assert float(cos(jnp.asarray(0))) == 0.0
        assert float(cos(jnp.asarray(10))) == pytest.approx(1.0)
        assert float(cos(jnp.asarray(100))) == pytest.approx(0.1, abs=1e-6)
        lin = linear_schedule(1.0, 10, 110)
        assert float(lin(jnp.asarray(60))) == pytest.approx(0.5)

    def test_clipping_bounds_update(self):
        opt = AdamW(schedule=lambda c: 1e-2, clip_norm=1.0)
        params = {"w": jnp.ones((8, 8))}
        state = opt.init(params)
        grads = {"w": jnp.full((8, 8), 1e6)}
        _, _, metrics = opt.update(grads, state, params)
        assert float(metrics["grad_norm"]) > 1e6

    def test_global_norm(self):
        tree = {"a": jnp.ones((3,)), "b": jnp.ones((4,))}
        assert float(global_norm(tree)) == pytest.approx(7 ** 0.5)


class TestDataPipeline:
    def test_deterministic_and_stateless(self):
        cfg = get_smoke("deepseek-7b")
        s1 = SyntheticStream(cfg, global_batch=4, seq_len=8, seed=5)
        s2 = SyntheticStream(cfg, global_batch=4, seq_len=8, seed=5,
                             start_step=2)
        np.testing.assert_array_equal(s1.batch_at(2)["tokens"],
                                      s2.batch_at(2)["tokens"])

    def test_host_sharding_partitions_batch(self):
        cfg = get_smoke("deepseek-7b")
        a = SyntheticStream(cfg, global_batch=4, seq_len=8, seed=0,
                            n_hosts=2, host_id=0)
        b = SyntheticStream(cfg, global_batch=4, seq_len=8, seed=0,
                            n_hosts=2, host_id=1)
        assert a.local_batch == b.local_batch == 2
        assert not np.array_equal(a.batch_at(0)["tokens"],
                                  b.batch_at(0)["tokens"])

    def test_batch_not_divisible_rejected(self):
        cfg = get_smoke("deepseek-7b")
        with pytest.raises(ValueError):
            SyntheticStream(cfg, global_batch=3, seq_len=8, n_hosts=2)


class TestEnsembleGang:
    def test_vmap_stack_matches_per_member(self):
        from repro.train.ensemble import train_ensemble, train_members
        members = [{"args:lr": lr, "args:seed": 0, "args:arch": "gemma3-1b",
                    "args:steps": 4, "args:batch": 2, "args:seq": 16}
                   for lr in (1e-3, 3e-3)]
        a = train_members(members)
        b = train_ensemble(members)
        np.testing.assert_allclose(a, b, atol=1e-4)

    def test_heterogeneous_members_rejected(self):
        from repro.train.ensemble import train_ensemble
        members = [{"args:arch": "gemma3-1b", "args:seq": 16},
                   {"args:arch": "gemma3-1b", "args:seq": 32}]
        with pytest.raises(ValueError):
            train_ensemble(members)


class TestDonationSafety:
    def test_master_does_not_alias_fp32_params(self):
        """fp32 params: master must be a COPY or donation breaks
        (regression: 'Attempt to donate the same buffer twice')."""
        cfg = get_smoke("gemma3-1b")           # param_dtype float32
        opt = AdamW(schedule=cosine_schedule(1e-3, 2, 10))
        state = init_train_state(cfg, opt, KEY)
        step = jax.jit(make_train_step(cfg, opt), donate_argnums=(0,))
        toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
        batch = {"tokens": toks, "labels": jnp.roll(toks, -1, axis=1)}
        state, m = step(state, batch)          # would raise on aliasing
        assert bool(jnp.isfinite(m["loss"]))
