"""MoE dispatch-strategy equivalence: einsum vs ragged vs sorted, plus
the shard_map path under an ambient mesh."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import AxisType

from repro.configs import get_smoke
from repro.models import Model, synthetic_batch
from repro.models.moe import moe_ragged, moe_sorted_local

KEY = jax.random.PRNGKey(5)


def toy_moe(T=64, D=32, E=8, F=16):
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (T, D), jnp.float32)
    p = {"router": jax.random.normal(ks[1], (D, E)) * 0.1,
         "wi_gate": jax.random.normal(ks[2], (E, D, F)) * 0.1,
         "wi_up": jax.random.normal(ks[3], (E, D, F)) * 0.1,
         "wo": jax.random.normal(ks[4], (E, F, D)) * 0.1}
    return x, p, E


class TestSortedDispatch:
    def test_sorted_matches_ragged_when_no_drops(self):
        x, p, e = toy_moe()
        o1, a1 = moe_sorted_local(x, p, n_experts=e, top_k=2, act="silu",
                                  router_renorm=False,
                                  compute_dtype=jnp.float32,
                                  capacity_factor=16.0)
        o2, _ = moe_ragged(x, p, n_experts=e, top_k=2, act="silu",
                           router_renorm=False, compute_dtype=jnp.float32)
        np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                                   atol=1e-5)
        assert float(a1["dropped"]) == 0.0

    def test_sorted_reports_drops_at_tight_capacity(self):
        # route everything to one expert → capacity must overflow
        x, p, e = toy_moe(T=512)
        p = dict(p)
        p["router"] = jnp.zeros_like(p["router"]).at[:, 0].set(10.0)
        _, aux = moe_sorted_local(x, p, n_experts=e, top_k=1, act="silu",
                                  router_renorm=False,
                                  compute_dtype=jnp.float32,
                                  capacity_factor=1.0)
        assert float(aux["dropped"]) > 0.0

    def test_gradients_flow(self):
        x, p, e = toy_moe()

        def loss(p):
            o, _ = moe_sorted_local(x, p, n_experts=e, top_k=2, act="silu",
                                    router_renorm=False,
                                    compute_dtype=jnp.float32)
            return jnp.sum(o ** 2)

        g = jax.grad(loss)(p)
        for leaf in jax.tree.leaves(g):
            assert bool(jnp.isfinite(leaf).all())
        assert float(jnp.abs(g["wi_gate"]).max()) > 0


class TestShardMapPath:
    def test_ragged_dispatch_under_ambient_mesh(self):
        """dispatch='ragged' + active mesh with a model axis routes
        through moe_ragged_sharded (shard_map)."""
        cfg = dataclasses.replace(get_smoke("olmoe-1b-7b"),
                                  moe_dispatch="ragged")
        m = Model(cfg)
        params = m.init(KEY)
        batch = synthetic_batch(cfg, 2, 32, KEY)
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             axis_types=(AxisType.Auto,) * 2)
        with jax.set_mesh(mesh):
            loss, aux = jax.jit(lambda p, b: m.loss(p, b))(params, batch)
        assert bool(jnp.isfinite(loss))
        # agrees with the local (no-mesh) ragged path
        loss2, _ = jax.jit(lambda p, b: m.loss(p, b))(params, batch)
        assert abs(float(loss) - float(loss2)) < 5e-3

    def test_einsum_vs_sorted_end_to_end(self):
        cfg_e = dataclasses.replace(get_smoke("qwen2-moe-a2.7b"),
                                    capacity_factor=8.0)
        cfg_s = dataclasses.replace(cfg_e, moe_dispatch="ragged")
        me, ms = Model(cfg_e), Model(cfg_s)
        params = me.init(KEY)
        batch = synthetic_batch(cfg_e, 2, 32, KEY)
        le, _ = me.loss(params, batch)
        ls, _ = ms.loss(params, batch)
        assert abs(float(le) - float(ls)) < 5e-3
