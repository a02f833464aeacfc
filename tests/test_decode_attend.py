"""`decode_attend` against a float32 reference that repeats the KV heads,
and a structural guard: the grouped contraction never builds the
repeated cache nor a float32 copy of it."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models.attention import decode_attend

from test_decode_hotpath import LOOP_TOL

B, S, D = 4, 24, 16
HEADS = [(32, 8), (8, 1), (4, 4)]           # GQA, MQA, MHA
LENS = [1, S, 7, S - 3]                     # ragged, 1 and S included


def _inputs(nh, kv, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    q = jax.random.normal(ks[0], (B, 1, nh, D)).astype(dtype)
    k = jax.random.normal(ks[1], (B, S, kv, D)).astype(dtype)
    v = jax.random.normal(ks[2], (B, S, kv, D)).astype(dtype)
    return q, k, v, jnp.asarray(LENS, jnp.int32)


def _reference(q, k, v, lens, window, round_p):
    """Float32 attention over the cache repeated to every query head.
    `round_p` rounds the probabilities to the dtype of the query and the
    cache before the value contraction, as the MXU takes its operands."""
    nh, kv = q.shape[2], k.shape[2]
    kf = jnp.repeat(k.astype(jnp.float32), nh // kv, axis=2)
    vf = jnp.repeat(v.astype(jnp.float32), nh // kv, axis=2)
    s = jnp.einsum("bhd,bshd->bhs", q[:, 0].astype(jnp.float32), kf,
                   precision="highest") / np.sqrt(q.shape[-1])
    pos = jnp.arange(k.shape[1])[None]
    valid = pos < lens[:, None]
    if window:
        valid &= pos >= lens[:, None] - window
    p = jax.nn.softmax(jnp.where(valid[:, None], s, -jnp.inf), axis=-1)
    if round_p:
        p = p.astype(jnp.result_type(q, k)).astype(jnp.float32)
    out = jnp.einsum("bhs,bshd->bhd", p, vf, precision="highest")
    return np.asarray(out[:, None]), np.asarray(
        jnp.einsum("bhs,bshd->bhd", p, jnp.abs(vf))[:, None])


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("nh,kv", HEADS)
def test_decode_attend_matches_repeated_reference(nh, kv, window, dtype):
    q, k, v, lens = _inputs(nh, kv, jnp.dtype(dtype))
    got = decode_attend(q, k, v, lens, window=window)
    assert got.shape == q.shape and got.dtype == q.dtype
    got = np.asarray(got.astype(jnp.float32))
    want, _ = _reference(q, k, v, lens, window, round_p=True)
    np.testing.assert_allclose(got, want, **LOOP_TOL[dtype])
    # Against unrounded probabilities the only error is their rounding
    # and the output's: u * sum(p |v|) + u * |out| (u: unit roundoff).
    exact, mass = _reference(q, k, v, lens, window, round_p=False)
    u = float(jnp.finfo(jnp.dtype(dtype)).eps) / 2
    assert np.all(np.abs(got - exact)
                  <= u * (mass + np.abs(exact)) + 1e-5)


def _avals(jaxpr):
    """Every intermediate value of a jaxpr, sub-jaxprs included."""
    for eqn in jaxpr.eqns:
        yield from (o.aval for o in eqn.outvars)
        for param in eqn.params.values():
            sub = getattr(param, "jaxpr", param)
            if hasattr(sub, "eqns"):
                yield from _avals(sub)


@pytest.mark.parametrize("window", [0, 5])
@pytest.mark.parametrize("nh,kv", [(32, 8), (8, 1)])
def test_decode_attend_reads_strip_as_stored(nh, kv, window):
    """No value has the repeated cache's size, in any layout, and no
    float32 value has the bf16 strip's size."""
    args = _inputs(nh, kv, jnp.bfloat16)
    closed = jax.make_jaxpr(
        lambda *a: decode_attend(*a, window=window))(*args)
    avals = list(_avals(closed.jaxpr))
    assert avals
    strip = B * S * kv * D
    for aval in avals:
        size = int(np.prod(aval.shape))
        assert size != B * S * nh * D, aval              # repeated K or V
        assert not (aval.dtype == jnp.float32 and size == strip), aval
