"""Mamba-2's depthwise causal conv (``models/ssm.py:_causal_conv``).

Checked against a plain NumPy per-tap float32 reference, forward and
gradients, and step by step through the streaming state.  A trace of
the train-time conv at mamba2-780m's width guards against the gathered
(B, S, K, C) window and its scatter-add gradient coming back.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.ssm import _causal_conv

# (batch, seq, channels, kernel width): channels off the 128-lane tile
SHAPES = [(2, 16, 200, 4), (1, 33, 96, 2), (3, 8, 130, 4)]
TOLS = {jnp.float32: 1e-5, jnp.bfloat16: 1e-2}


def _inputs(shape, dtype, seed=0):
    bsz, s, c, k = shape
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.standard_normal((bsz, s, c)), jnp.float32).astype(dtype)
    w = jnp.asarray(rng.standard_normal((k, c)) * 0.5, jnp.float32)
    b = jnp.asarray(rng.standard_normal(c), jnp.float32)
    # a cotangent that the output's dtype holds exactly
    ct = jnp.asarray(rng.standard_normal((bsz, s, c)),
                     jnp.float32).astype(dtype).astype(jnp.float32)
    return x, w, b, ct


def _ref(x, w, b, ct):
    """y_t = b + Σ_k w_k · x_{t-K+1+k} and its gradients, per tap in f32."""
    x, w, b, ct = (np.asarray(a, np.float32) for a in (x, w, b, ct))
    k, s = w.shape[0], x.shape[1]
    pad = np.concatenate([np.zeros_like(x[:, :k - 1]), x], axis=1)
    y = sum(pad[:, i:i + s] * w[i] for i in range(k)) + b
    dpad = np.zeros_like(pad)
    for i in range(k):
        dpad[:, i:i + s] += ct * w[i]
    dw = np.stack([(pad[:, i:i + s] * ct).sum((0, 1)) for i in range(k)])
    return y, dpad[:, k - 1:], dw, ct.sum((0, 1))


def _close(got, want, tol):
    got, want = np.asarray(got, np.float32), np.asarray(want, np.float32)
    np.testing.assert_allclose(got, want, rtol=tol,
                               atol=tol * np.abs(want).max())


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", SHAPES)
def test_conv_matches_reference(shape, dtype):
    x, w, b, ct = _inputs(shape, dtype)
    y, state = _causal_conv(x, w, b)
    assert state is None and y.dtype == dtype and y.shape == x.shape

    def loss(x, w, b):
        return jnp.sum(_causal_conv(x, w, b)[0].astype(jnp.float32) * ct)

    dx, dw, db = jax.grad(loss, argnums=(0, 1, 2))(x, w, b)
    y_ref, dx_ref, dw_ref, db_ref = _ref(x, w, b, ct)
    tol = TOLS[dtype]
    _close(y, y_ref, tol)
    assert dx.dtype == dtype
    _close(dx, dx_ref, tol)
    # w and b are f32 whatever x is; only the summation order differs
    _close(dw, dw_ref, 1e-5)
    _close(db, db_ref, 1e-5)


@pytest.mark.parametrize("shape", SHAPES)
def test_streaming_reproduces_full_sequence(shape):
    """S tokens one at a time through the state give the full output
    (the same products summed in the same order), and the last state
    is the last K-1 inputs."""
    bsz, s, c, k = shape
    x, w, b, _ = _inputs(shape, jnp.bfloat16, seed=1)
    full, _ = _causal_conv(x, w, b)
    # the decode cache holds the conv state in f32 (init_ssm_cache)
    state = jnp.zeros((bsz, k - 1, c), jnp.float32)
    steps = []
    for t in range(s):
        y, state = _causal_conv(x[:, t:t + 1], w, b, state)
        assert y.shape == (bsz, 1, c) and state.shape == (bsz, k - 1, c)
        steps.append(y)
    np.testing.assert_array_equal(
        np.asarray(jnp.concatenate(steps, axis=1), np.float32),
        np.asarray(full, np.float32))
    np.testing.assert_array_equal(np.asarray(state),
                                  np.asarray(x[:, s - (k - 1):], np.float32))


def _eqns(jaxpr):
    """Every equation of ``jaxpr`` and of the jaxprs nested in it."""
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for sub in v if isinstance(v, (list, tuple)) else (v,):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _eqns(sub)


@pytest.mark.parametrize("pass_", ["forward", "grad"])
def test_train_conv_builds_no_window(pass_):
    """At mamba2-780m's train width: no gather, no scatter-add, and no
    array as large as a (B, S, K, C) window."""
    bsz, s, c, k = 4, 2048, 3328, 4
    args = (jax.ShapeDtypeStruct((bsz, s, c), jnp.bfloat16),
            jax.ShapeDtypeStruct((k, c), jnp.float32),
            jax.ShapeDtypeStruct((c,), jnp.float32))

    def fwd(x, w, b):
        return _causal_conv(x, w, b)[0]

    def loss(x, w, b):
        return jnp.sum(fwd(x, w, b).astype(jnp.float32))

    fn = fwd if pass_ == "forward" else jax.grad(loss, argnums=(0, 1, 2))
    eqns = list(_eqns(jax.make_jaxpr(fn)(*args).jaxpr))
    prims = {e.primitive.name for e in eqns}
    assert not prims & {"gather", "scatter-add", "scatter"}, prims
    shapes = {tuple(v.aval.shape) for e in eqns for v in e.outvars}
    assert (bsz, s, k, c) not in shapes
    assert max(int(np.prod(sh)) for sh in shapes) <= bsz * (s + k - 1) * c
