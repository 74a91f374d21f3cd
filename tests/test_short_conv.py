"""LFM2's gated short convolution (``ops/causal_conv.py::short_conv``):
the Pallas kernel pair of ``ops/pallas/causal_conv.py`` in interpret mode
(``PADDLE_PALLAS_FORCE=1``) against the XLA reference in float32 — the
product ``c * conv(b * u)`` and the gradients of ``bcu`` and ``conv_w`` —
over 2, 3 and 4 taps, bfloat16 and float32, a T of one tile and one
chunk, a T of several tiles, chunks and channel blocks (the halo in the
forward, the carry in the backward) and a T no tile divides (the XLA
path, whole).  ``bcu`` is ``(3, B, T, D)``, the sections b, c, u on the
leading axis, as the in-projection writes it.  Every case has three batch rows,
the middle one nought in input and cotangent: nothing of a neighbour may
reach it.
"""
from typing import NamedTuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh

from paddle_tpu.ops import causal_conv as conv_module
from paddle_tpu.ops import pallas
from paddle_tpu.ops.causal_conv import short_conv
from paddle_tpu.ops.pallas import causal_conv as kernels

B, D = 3, 256


class Length(NamedTuple):
    T: int
    block_t: int                # the most tokens a tile, as the test sets it
    block_c: int                # the most channels a block
    rows: int                   # the most rows a chunk
    impl: str


# one tile of two chunks and one channel block; three tiles of two chunks
# each over two channel blocks; 40 = 2.5 tiles of 16: the XLA path
LENGTHS = {"one-tile": Length(64, 512, 512, 32, "interpret"),
           "several-tiles": Length(96, 32, 128, 16, "interpret"),
           "no-tile": Length(40, 512, 512, 16, "xla")}


def _inputs(T, taps, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 3)
    middle = jnp.asarray([1.0, 0.0, 1.0])[:, None, None]
    bcu = (jax.random.normal(ks[0], (3, B, T, D)) * middle).astype(dtype)
    w = (0.5 * jax.random.normal(ks[1], (taps, D))).astype(dtype)
    cot = (jax.random.normal(ks[2], (B, T, D)) * middle).astype(dtype)
    return bcu, w, cot


def _run(fn, bcu, w, cot):
    """-> (the product, {bcu, conv_w}: the gradients under ``cot``), all
    float32."""
    def loss(bcu, w):
        y = fn(bcu, w)
        return jnp.sum(y.astype(jnp.float32) * cot.astype(jnp.float32)), y
    (_, y), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(bcu, w)
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    return f32(y), dict(zip(("bcu", "conv_w"), map(f32, grads)))


@pytest.fixture(scope="module", params=[
    (length, taps, dtype) for length in LENGTHS for taps in (2, 3, 4)
    for dtype in ("bfloat16", "float32")],
    ids=lambda p: "-".join(map(str, p)))
def both(request):
    """The op under the kernels' plan and the float32 reference, each
    (product, gradients), with the case."""
    name, taps, dtype = request.param
    length = LENGTHS[name]
    bcu, w, cot = _inputs(length.T, taps, jnp.dtype(dtype))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PADDLE_PALLAS_FORCE", "1")
        mp.setattr(kernels, "_BLOCK_T", length.block_t)
        mp.setattr(kernels, "_BLOCK_C", length.block_c)
        mp.setattr(kernels, "_ROWS", length.rows)
        before = pallas.selections().get(f"short_conv.{length.impl}", 0)
        got = _run(short_conv, bcu, w, cot)
        assert pallas.selections()[f"short_conv.{length.impl}"] \
            == before + 1
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    want = _run(conv_module._short_reference, f32(bcu), f32(w), f32(cot))
    # a rounding of the result's dtype: the kernels compute in float32,
    # the XLA path rounds every product and sum to the operands' dtype
    tol = 2e-6 if dtype == "float32" else \
        2.0 ** -8 if length.impl == "interpret" else 2.0 ** -5
    return got, want, length, tol


def _close(got, want, tol, sums: int = 8):
    """Within ``tol`` of the largest entry (in float32, ``sums`` terms
    summed in another order)."""
    if tol < 1e-4:
        tol *= sums
    scale = float(np.max(np.abs(want))) or 1.0
    np.testing.assert_allclose(got, want, rtol=tol, atol=tol * scale)


def test_the_product_equals_the_reference(both):
    (got, _), (want, _), length, tol = both
    assert got.shape == (B, length.T, D)
    _close(got, want, tol)


@pytest.mark.parametrize("section", ("b", "c", "u"))
def test_the_gradient_of_each_section_equals_the_reference(both, section):
    """d(bcu) is one array, db, dc, du on its leading axis as the
    in-projection's transpose reads it."""
    (_, got), (_, want), _, tol = both
    at = "bcu".index(section)
    assert got["bcu"].shape == want["bcu"].shape
    _close(got["bcu"][at], want["bcu"][at], tol)


def test_the_gradient_of_the_taps_equals_the_reference(both):
    (_, got), (_, want), length, tol = both
    assert got["conv_w"].shape == want["conv_w"].shape
    _close(got["conv_w"], want["conv_w"], tol, sums=B * length.T)


def test_the_first_rows_have_no_history(both):
    """Rows 0 .. taps - 2 see fewer taps than there are: nothing stands
    in for ``s_{<0}`` (the halo block there holds the tile's own rows)."""
    (got, got_g), (want, want_g), _, tol = both
    taps = want_g["conv_w"].shape[0]
    _close(got[:, :taps], want[:, :taps], tol)
    _close(got_g["bcu"][:, :, :taps], want_g["bcu"][:, :, :taps], tol)


def test_the_rows_at_every_tile_edge(both):
    """Three rows either side of every tile's and chunk's edge: the
    forward reads across it backwards (the halo, the loop's carry), the
    backward forwards (the carried ``g c``)."""
    (got, got_g), (want, want_g), length, tol = both
    edges = range(length.rows, length.T, length.rows)
    rows = np.concatenate([np.arange(e - 3, e + 3) for e in edges])
    _close(got[:, rows], want[:, rows], tol)
    _close(got_g["bcu"][:, :, rows], want_g["bcu"][:, :, rows], tol)


def test_nothing_crosses_batch_rows(both):
    """The middle row is nought in and under a nought cotangent: its
    product and its gradient are nought whatever its neighbours hold."""
    (got, got_g), _, _, _ = both
    assert not np.any(got[1]) and not np.any(got_g["bcu"][:, 1])
    assert np.any(got_g["bcu"][:, 0]) and np.any(got_g["bcu"][:, 2])


# ---------------------------------------------------------------------------
# the plan: what it takes, what it refuses, and what is counted
# ---------------------------------------------------------------------------
def _plan(T=64, d=D, taps=3, dtype=jnp.bfloat16):
    return kernels.short_plan(B, T, d, taps, dtype, interpret=True)


def test_the_plan_at_the_benchmark_s_size():
    """(3, 4, 8192, 2048), 3 taps: tiles of 1024 tokens, channel blocks
    of 512 lanes — 4 a section —, chunks of 256 rows."""
    plan = kernels.short_plan(4, 8192, 2048, 3, jnp.bfloat16,
                              interpret=False)
    assert plan == kernels.Plan(kernels._BLOCK_T, kernels._BLOCK_C,
                                kernels._ROWS, False)


@pytest.mark.parametrize("why,kw", [
    ("a D that is no whole lane blocks", dict(d=192)),
    ("a T that is no whole 16-row tiles", dict(T=40)),
    ("more history than the carry holds", dict(taps=10)),
    ("a dtype the kernels do not read", dict(dtype=jnp.float16)),
], ids=lambda x: x.replace(" ", "-") if isinstance(x, str) else "")
def test_the_plan_refuses(why, kw):
    assert _plan() is not None
    assert _plan(**kw) is None, why


@pytest.mark.parametrize("d,block_c", [(128, 128), (384, 384), (640, 128),
                                       (2048, 512)])
def test_a_channel_block_is_whole_lanes_that_divide_a_section(d, block_c):
    plan = _plan(d=d)
    assert plan.block_c == block_c and d % plan.block_c == 0


@pytest.mark.parametrize("force,T,d,impl", [
    ("1", 32, 128, "interpret"), ("1", 40, 128, "xla"),
    ("1", 32, 192, "xla"), ("0", 32, 128, "xla")],
    ids=["forced", "refused-T", "refused-D", "off-the-tpu"])
def test_the_selection_is_counted(monkeypatch, force, T, d, impl):
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", force)
    bcu = jnp.zeros((3, 1, T, d), jnp.float32)
    w = jnp.zeros((3, d), jnp.float32)
    before = pallas.selections()
    # a function of its own: a trace cached under another case counts
    # nothing
    y = jax.eval_shape(lambda x, w: short_conv(x, w), bcu, w)
    assert y.shape == (1, T, d)
    after = pallas.selections()
    changed = {k: after[k] - before.get(k, 0) for k in after
               if k.startswith("short_conv.")
               and after[k] != before.get(k, 0)}
    assert changed == {f"short_conv.{impl}": 1}


def test_the_mixer_s_convolution_is_not_counted_here(monkeypatch):
    """Two entries, two counters: LFM2's call counts ``short_conv.*``
    and nothing of ``causal_conv.*``, the delta-rule mixer's."""
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    before = pallas.selections()
    jax.eval_shape(lambda x, w: short_conv(x, w),
                   jnp.zeros((3, 1, 32, 128), jnp.float32),
                   jnp.zeros((3, 128), jnp.float32))
    after = pallas.selections()
    assert {k for k in after if after[k] != before.get(k, 0)} \
        == {"short_conv.interpret"}


def test_under_a_mesh_the_kernels_run_per_shard(monkeypatch):
    """B over ``dp`` = 2: the taps' gradient is summed over the shards."""
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    bcu = jax.random.normal(ks[0], (3, 2, 32, D))
    w = 0.5 * jax.random.normal(ks[1], (3, D))
    cot = jax.random.normal(ks[2], (2, 32, D))

    def loss(mesh):
        def fn(bcu, w):
            y = short_conv(bcu, w, mesh=mesh, batch_axes=("dp",))
            return jnp.sum(y * cot)
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))(bcu, w)

    (want, want_g), (got, got_g) = loss(None), loss(mesh)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
