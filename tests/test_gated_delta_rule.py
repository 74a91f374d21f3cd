"""The chunked gated delta rule (``ops/gated_delta_rule.py``) against the
recurrence token by token, in float32 on the CPU: the output and all five
gradients (q, k, v, g, beta), at a length the chunk does not divide, with
a mild gate and with a gate down to -20 a token — where ``exp(G_i)
exp(-G_j)`` would overflow and the chunked form, which only ever takes
``exp(G_i - G_j)`` with i >= j, must not.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops.gated_delta_rule import (gated_delta_rule,
                                             gated_delta_rule_reference)

NAMES = ("q", "k", "v", "g", "beta")
B, T, H, DK, DV, CHUNK = 2, 37, 3, 8, 6, 8      # 37 = 4 chunks of 8 + 5


def _inputs(seed, g_min, T=T, heads_k=H):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, T, heads_k, DK))) / np.sqrt(DK)
    k = unit(jax.random.normal(ks[1], (B, T, heads_k, DK)))
    v = jax.random.normal(ks[2], (B, T, H, DV))
    g = g_min * jax.random.uniform(ks[3], (B, T, H))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, H)))
    return q, k, v, g, beta


@pytest.fixture(scope="module", params=[-0.5, -20.0],
                ids=["mild-gate", "gate-to-minus-20"])
def both(request):
    """(outputs, gradients) of the chunked rule and of the recurrence,
    each under the same random cotangent."""
    x = _inputs(0, request.param)
    w = jax.random.normal(jax.random.PRNGKey(9), (B, T, H, DV))

    def run(fn):
        def loss(*a):
            o = fn(*a)
            return jnp.sum(o * w), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*x)
        return o, dict(zip(NAMES, grads))

    return (run(lambda *a: gated_delta_rule(*a, chunk=CHUNK)),
            run(gated_delta_rule_reference))


def test_the_output_equals_the_recurrence(both):
    (o, _), (want, _) = both
    assert o.shape == (B, T, H, DV) and bool(jnp.all(jnp.isfinite(o)))
    # float32 sums in another order: a chunk's 8 writes at once
    np.testing.assert_allclose(o, want, rtol=1e-4, atol=2e-6)


@pytest.mark.parametrize("name", NAMES)
def test_every_gradient_equals_the_recurrence_s(both, name):
    (_, got), (_, want) = both
    assert bool(jnp.all(jnp.isfinite(got[name])))
    scale = float(jnp.max(jnp.abs(want[name])))
    np.testing.assert_allclose(got[name], want[name], rtol=1e-3,
                               atol=1e-5 * scale)


def test_the_strong_gate_reaches_the_overflow_arm():
    """In the second case a chunk's cumulative log-decay passes float32's
    exp range: ``exp(-G_j)`` alone would be inf."""
    g = np.asarray(_inputs(0, -20.0)[3])[:, :T - T % CHUNK]
    G = np.cumsum(g.reshape(B, -1, CHUNK, H), axis=2)
    assert g.min() < -19 and G.min() < -89    # exp(89) > float32's largest
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(np.float32(-G.min())))


@pytest.mark.parametrize("T_,chunk", [(16, 8), (5, 8), (33, 16)],
                         ids=["divides", "shorter-than-a-chunk", "tail"])
def test_any_length(T_, chunk):
    x = _inputs(1, -2.0, T=T_)
    np.testing.assert_allclose(
        gated_delta_rule(*x, chunk=chunk), gated_delta_rule_reference(*x),
        rtol=1e-4, atol=2e-6)


def test_key_heads_serve_groups_of_value_heads():
    """3 value heads over 1 key head: as if q and k were repeated."""
    q, k, v, g, beta = _inputs(2, -1.0, heads_k=1)
    rep = lambda x: jnp.repeat(x, H, axis=2)   # noqa: E731
    f = lambda q, k: jnp.sum(jnp.sin(   # noqa: E731
        gated_delta_rule(q, k, v, g, beta, chunk=CHUNK)))
    f_rep = lambda q, k: jnp.sum(jnp.sin(gated_delta_rule_reference(   # noqa
        rep(q), rep(k), v, g, beta)))
    np.testing.assert_allclose(f(q, k), f_rep(q, k), rtol=1e-5)
    for got, want in zip(jax.grad(f, (0, 1))(q, k),
                         jax.grad(f_rep, (0, 1))(q, k)):
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-6)


def test_bfloat16_operands_keep_the_state_in_float32():
    """A bf16 step hands bf16 q, k, v; g, the decays and the state stay
    float32: the result is bf16 and near the float32 rule (bf16 has 8
    bits: a percent of the largest output)."""
    q, k, v, g, beta = _inputs(3, -2.0)
    o = gated_delta_rule(*(a.astype(jnp.bfloat16) for a in (q, k, v)),
                         g, beta, chunk=CHUNK)
    assert o.dtype == jnp.bfloat16
    want = gated_delta_rule_reference(q, k, v, g, beta)
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - want))) \
        < 0.03 * float(jnp.max(jnp.abs(want)))
