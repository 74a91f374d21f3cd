"""Rotate-half RoPE handed over head-major (``ops/rope.py``): the Pallas
kernel pair of ``ops/pallas/rope.py`` in interpret mode
(``PADDLE_PALLAS_FORCE=1``) against ``rope_rotate_half``, the XLA math —
the forward and the VJP (against autodiff of the reference) — over both
of Mellum2's layer kinds' tables (theta 500 000; YaRN with cos and sin
times 1.2773), 32 query heads and 4 key heads of 128, bfloat16 and
float32, a T of one tile and a T of several tiles and chunks.

**The forward is equal to the bit:** the kernel computes ``x cos +
roll(x, 64) sin'`` with the sign of ``concatenate([-x2, x1])`` in
``sin'``, the reference ``x cos + concatenate([-x2, x1]) sin`` — the same
float32 products and sum of the same float32 tables, rounded once to the
operands' dtype.  **The VJP is within one rounding:** the kernel's ``dy
cos - roll(dy, 64) sin'`` is autodiff's sum term for term, but XLA's CPU
compiler contracts the kernel's multiply and subtract into one fused
multiply-add and not autodiff's, whose sum comes through a concatenate:
a quarter of the float32 results differ in their last bits (the kernel's
the nearer to the exact sum), a few bfloat16 results in a 1-ulp rounding.
"""
from typing import NamedTuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from paddle_tpu.models.sparse_blocks import rope_angles
from paddle_tpu.ops import pallas
from paddle_tpu.ops.pallas import rope as kernels
from paddle_tpu.ops.rope import rope_rotate_half, rope_to_heads

B, H, K, HD = 2, 32, 4, 128
THETA = 500000.0
YARN = (16.0, 8192, 32.0, 1.0)
YARN_FACTOR = 1.2772588722239782


class Length(NamedTuple):
    T: int
    block_t: int                # the most tokens a tile, as the test sets it
    rows: int                   # the most rows a chunk


# one tile of two chunks; three tiles of two chunks each
LENGTHS = {"one-tile": Length(64, 1024, 32),
           "several-tiles": Length(96, 32, 16)}
KINDS = {"window": lambda T: (rope_angles(T, THETA, HD), 1.0),
         "yarn": lambda T: (rope_angles(T, THETA, HD, YARN), YARN_FACTOR)}


def _inputs(T, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    q, k = (jax.random.normal(kk, (B, T, n * HD)).astype(dtype)
            for kk, n in zip(ks[:2], (H, K)))
    dq, dk = (jax.random.normal(kk, (B, n, T, HD)).astype(dtype)
              for kk, n in zip(ks[2:], (H, K)))
    return q, k, (dq, dk)


def _reference(q, k, ang, scale):
    """``rope_rotate_half`` on (B, T, heads, hd), moved head-major."""
    def one(x):
        b, t, f = x.shape
        return jnp.swapaxes(rope_rotate_half(
            x.reshape(b, t, f // HD, HD), ang, scale), 1, 2)
    return one(q), one(k)


def _run(fn, q, k, cot):
    """-> ((q, k) turned, (dq, dk): the VJP under ``cot``), as numpy in
    the operands' dtype."""
    out, vjp = jax.vjp(fn, q, k)
    grads = vjp(cot)
    return tuple(map(np.asarray, out)), tuple(map(np.asarray, grads))


@pytest.fixture(scope="module", params=[
    (length, kind, dtype) for length in LENGTHS for kind in KINDS
    for dtype in ("bfloat16", "float32")],
    ids=lambda p: "-".join(p))
def both(request):
    """The entry under the kernels' plan and the reference, each (outputs,
    gradients)."""
    name, kind, dtype = request.param
    length = LENGTHS[name]
    ang, scale = KINDS[kind](length.T)
    q, k, cot = _inputs(length.T, jnp.dtype(dtype))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PADDLE_PALLAS_FORCE", "1")
        mp.setattr(kernels, "_BLOCK_T", length.block_t)
        mp.setattr(kernels, "_ROWS", length.rows)
        before = pallas.selections().get("rope.interpret", 0)
        got = _run(jax.jit(lambda q, k: rope_to_heads(q, k, ang, scale)),
                   q, k, cot)
        assert pallas.selections()["rope.interpret"] == before + 1
    want = _run(jax.jit(lambda q, k: _reference(q, k, ang, scale)),
                q, k, cot)
    return got, want, (cot, ang, scale)


def test_the_forward_equals_the_reference_to_the_bit(both):
    (got, _), (want, _), _ = both
    for g, w, n in zip(got, want, (H, K)):
        assert g.shape == w.shape and g.shape[1] == n
        assert g.dtype == w.dtype
        np.testing.assert_array_equal(g, w)


def _ulps(a, b):
    """How many values of the dtype lie between a and b."""
    bits = {2: np.int16, 4: np.int32}[a.dtype.itemsize]

    def ordered(x):
        i = x.view(bits).astype(np.int64)
        return np.where(i < 0, np.iinfo(bits).min - i, i)
    return np.abs(ordered(a) - ordered(b))


def _terms(dy, ang, scale):
    """|dy cos| + |dy' sin| of each result of the backward, token-major
    like it, in float64: dy (B, n, T, hd), dy' its halves swapped."""
    both = np.concatenate([ang, ang], -1)
    dy = np.abs(np.asarray(dy, np.float64))
    t = dy * np.abs(scale * np.cos(both)) \
        + np.roll(dy, HD // 2, -1) * np.abs(scale * np.sin(both))
    b, n, T, hd = t.shape
    return np.swapaxes(t, 1, 2).reshape(b, T, n * hd)


def test_the_vjp_is_autodiff_of_the_reference_within_one_rounding(both):
    """bfloat16: one ulp at most.  float32: two roundings of the terms,
    ``2^-23 (|dy cos| + |dy' sin|)`` — where the terms cancel, that is
    many ulps of the small result."""
    (_, got), (_, want), (cot, ang, scale) = both
    for g, w, n, dy in zip(got, want, (H, K), cot):
        assert g.shape == w.shape and g.shape[-1] == n * HD
        assert g.dtype == w.dtype
        if g.dtype == np.float32:
            assert np.all(np.abs(g.astype(np.float64) - w)
                          <= 2.0 ** -23 * _terms(dy, ang, scale))
        else:
            assert _ulps(g, w).max() <= 1


# ---------------------------------------------------------------------------
# the plan: what it takes, what it refuses, and what is counted
# ---------------------------------------------------------------------------
def test_the_plan_at_the_benchmark_s_size():
    """Mellum2's (4, 8192) tokens, 32 q and 4 k heads of 128, bf16: tiles
    of 1024 tokens, blocks of 4 heads (512 lanes: k is one block, q
    eight), chunks of 64 rows."""
    plan = kernels.plan(8192, 32, 4, 128, jnp.bfloat16, interpret=False)
    assert plan == kernels.Plan(1024, 4, 64, False)
    # float32 takes half the tokens a tile: the same bytes
    assert kernels.plan(8192, 32, 4, 128, jnp.float32,
                        interpret=False).block_t == 512


@pytest.mark.parametrize("why,kw", [
    ("a head that is no whole lane block (LFM2's 64)", dict(hd=64)),
    ("a T that is no whole 16-row tiles", dict(T=40)),
    ("a dtype the kernels do not read", dict(dtype=jnp.float16)),
], ids=lambda x: x.split(" (")[0].replace(" ", "-")
    if isinstance(x, str) else "")
def test_the_plan_refuses(why, kw):
    args = dict(T=64, H=H, K=K, hd=HD, dtype=jnp.bfloat16)
    assert kernels.plan(**args, interpret=True) is not None
    assert kernels.plan(**dict(args, **kw), interpret=True) is None, why


@pytest.mark.parametrize("force,hd,T,impl", [
    ("1", 128, 32, "interpret"), ("1", 64, 32, "xla"),
    ("1", 128, 40, "xla"), ("0", 128, 32, "xla")],
    ids=["forced", "head-64", "T-40", "off-the-tpu"])
def test_the_selection_is_counted(monkeypatch, force, hd, T, impl):
    """Each choice counts ``rope.<impl>`` once; a refused shape runs the
    XLA math whole and hands over the same head-major shapes."""
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", force)
    q = jnp.zeros((1, T, 4 * hd), jnp.bfloat16)
    k = jnp.zeros((1, T, 2 * hd), jnp.bfloat16)
    ang = rope_angles(T, THETA, hd)
    before = pallas.selections()
    tq, tk = jax.eval_shape(lambda q, k: rope_to_heads(q, k, ang), q, k)
    assert (tq.shape, tk.shape) == ((1, 4, T, hd), (1, 2, T, hd))
    after = pallas.selections()
    changed = {n: after[n] - before.get(n, 0) for n in after
               if n.startswith("rope.") and after[n] != before.get(n, 0)}
    assert changed == {f"rope.{impl}": 1}


def test_a_head_of_64_runs_the_reference_whole(monkeypatch):
    """The fallback is ``rope_rotate_half`` itself, at LFM2's head size."""
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    T = 40
    q, k = (jax.random.normal(jax.random.PRNGKey(n), (B, T, n * 64))
            .astype(jnp.bfloat16) for n in (8, 2))
    ang = rope_angles(T, THETA, 64)
    got = rope_to_heads(q, k, ang)
    for g, x in zip(got, (q, k)):
        want = jnp.swapaxes(rope_rotate_half(
            x.reshape(B, T, -1, 64), ang), 1, 2)
        np.testing.assert_array_equal(np.asarray(g), np.asarray(want))


def test_under_a_mesh_the_kernels_run_per_shard(monkeypatch):
    """B over ``dp`` = 2: each shard turns its own rows."""
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    T = 32
    q, k, cot = _inputs(T, jnp.float32, seed=5)
    ang = rope_angles(T, THETA, HD, YARN)

    def run(mesh):
        return _run(jax.jit(lambda q, k: rope_to_heads(
            q, k, ang, YARN_FACTOR, mesh=mesh, batch_axes=("dp",))),
            q, k, cot)

    for a, b in zip(jax.tree.leaves(run(mesh)), jax.tree.leaves(run(None))):
        np.testing.assert_array_equal(a, b)
