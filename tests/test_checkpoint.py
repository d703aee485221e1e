"""Checkpoint save/restore: roundtrip, atomicity, pruning, elastic."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import ckpt
from repro.configs import get_smoke
from repro.optim.adamw import AdamW, cosine_schedule
from repro.train.step import init_train_state

KEY = jax.random.PRNGKey(3)


def small_state():
    cfg = get_smoke("deepseek-7b")
    opt = AdamW(schedule=cosine_schedule(1e-3, 5, 50))
    return cfg, opt, init_train_state(cfg, opt, KEY)


class TestRoundtrip:
    def test_save_restore_identical(self, tmp_path):
        _, _, state = small_state()
        ckpt.save(state, tmp_path, step=7)
        restored = ckpt.restore(state, tmp_path)
        for a, b in zip(jax.tree.leaves(state), jax.tree.leaves(restored)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

    def test_latest_step_and_pruning(self, tmp_path):
        _, _, state = small_state()
        for s in (1, 2, 3, 4, 5):
            ckpt.save(state, tmp_path, step=s, keep=2)
        assert ckpt.all_steps(tmp_path) == [4, 5]
        assert ckpt.latest_step(tmp_path) == 5

    def test_restore_specific_step(self, tmp_path):
        _, _, state = small_state()
        s1 = jax.tree.map(lambda x: x, state)
        ckpt.save(s1, tmp_path, step=1)
        s2 = jax.tree.map(
            lambda x: x + 1 if jnp.issubdtype(x.dtype, jnp.floating) else x,
            state)
        ckpt.save(s2, tmp_path, step=2)
        r1 = ckpt.restore(state, tmp_path, step=1)
        np.testing.assert_array_equal(
            np.asarray(jax.tree.leaves(r1)[0]),
            np.asarray(jax.tree.leaves(s1)[0]))

    def test_shape_mismatch_rejected(self, tmp_path):
        _, _, state = small_state()
        ckpt.save(state, tmp_path, step=1)
        bad = jax.tree.map(lambda x: jnp.zeros((3,) + x.shape, x.dtype),
                           state)
        with pytest.raises(ValueError):
            ckpt.restore(bad, tmp_path)

    def test_missing_dir_raises(self, tmp_path):
        _, _, state = small_state()
        with pytest.raises(FileNotFoundError):
            ckpt.restore(state, tmp_path / "nope")

    def test_no_tmp_dir_left_behind(self, tmp_path):
        _, _, state = small_state()
        ckpt.save(state, tmp_path, step=1)
        assert not list(tmp_path.glob("*.tmp"))


class TestElastic:
    def test_restore_onto_explicit_shardings(self, tmp_path):
        """Elastic restart: restore with a target sharding tree built for
        the current (1-device) mesh."""
        from repro.distributed import sharding as shd
        from repro.launch.mesh import make_local_mesh

        _, _, state = small_state()
        ckpt.save(state, tmp_path, step=3)
        mesh = make_local_mesh()
        shardings = shd.state_shardings(
            jax.eval_shape(lambda s: s, state), mesh)
        restored = ckpt.restore(state, tmp_path, shardings=shardings)
        leaf = jax.tree.leaves(restored["params"])[0]
        assert leaf.sharding is not None
        np.testing.assert_array_equal(
            np.asarray(leaf),
            np.asarray(jax.tree.leaves(state["params"])[0]))

    def test_training_resumes_from_checkpoint(self, tmp_path):
        """Save at step 2, keep training to 4; restart from ckpt and
        re-train — trajectories match (determinism of resume)."""
        from repro.data.pipeline import SyntheticStream
        from repro.train.step import make_train_step

        cfg, opt, state = small_state()
        step_fn = jax.jit(make_train_step(cfg, opt))
        stream = SyntheticStream(cfg, global_batch=2, seq_len=16, seed=1)

        losses_a = []
        for i in range(4):
            batch = {k: jnp.asarray(v) for k, v in stream.batch_at(i).items()}
            state, m = step_fn(state, batch)
            losses_a.append(float(m["loss"]))
            if i == 1:
                ckpt.save(state, tmp_path, step=2)

        restored = ckpt.restore(
            jax.eval_shape(lambda s: s, state), tmp_path, step=2)
        losses_b = []
        for i in range(2, 4):
            batch = {k: jnp.asarray(v) for k, v in stream.batch_at(i).items()}
            restored, m = step_fn(restored, batch)
            losses_b.append(float(m["loss"]))
        np.testing.assert_allclose(losses_a[2:], losses_b, rtol=1e-5)
