"""The delta-rule mixer's convolution (``ops/causal_conv.py``): the Pallas
kernel pair of ``ops/pallas/causal_conv.py`` in interpret mode
(``PADDLE_PALLAS_FORCE=1``) against the XLA reference in float32 — q, k,
v and the gradients of ``qkv`` and ``conv_w`` — over 3 and 4 taps,
bfloat16 and float32, a T of one tile and one chunk, a T of several tiles
and chunks (the halo in the forward, the carry in the backward) and a T
no tile divides (the XLA path, whole).  Every case has three batch rows,
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
from paddle_tpu.ops.causal_conv import gated_causal_conv
from paddle_tpu.ops.pallas import causal_conv as kernels

B, HEAD, N_QK, N_V = 3, 128, 256, 512
C = 2 * N_QK + N_V
KINDS = ("q", "k", "v")


class Length(NamedTuple):
    T: int
    block_t: int                # the most tokens a tile, as the test sets it
    rows: int                   # the most rows a chunk
    impl: str


# one tile of two chunks; three tiles of two chunks each; 40 = 2.5 tiles
# of 16: no tile, the XLA path
LENGTHS = {"one-tile": Length(64, 512, 32, "interpret"),
           "several-tiles": Length(96, 32, 16, "interpret"),
           "no-tile": Length(40, 512, 16, "xla")}


def _inputs(T, taps, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    middle = jnp.asarray([1.0, 0.0, 1.0])[:, None, None]
    qkv = (jax.random.normal(ks[0], (B, T, C)) * middle).astype(dtype)
    w = (0.5 * jax.random.normal(ks[1], (taps, C))).astype(dtype)
    cot = tuple((jax.random.normal(k, (B, T, n)) * middle).astype(dtype)
                for k, n in zip(ks[2:], (N_QK, N_QK, N_V)))
    return qkv, w, cot


def _run(fn, qkv, w, cot):
    """-> ({q, k, v}, {qkv, conv_w}: the gradients under ``cot``), all
    float32."""
    def loss(qkv, w):
        outs = fn(qkv, w)
        return sum(jnp.sum(o.astype(jnp.float32) * c.astype(jnp.float32))
                   for o, c in zip(outs, cot)), outs
    (_, outs), grads = jax.jit(jax.value_and_grad(
        loss, argnums=(0, 1), has_aux=True))(qkv, w)
    f32 = lambda x: np.asarray(x, np.float32)  # noqa: E731
    return (dict(zip(KINDS, map(f32, outs))),
            dict(zip(("qkv", "conv_w"), map(f32, grads))))


@pytest.fixture(scope="module", params=[
    (length, taps, dtype) for length in LENGTHS for taps in (3, 4)
    for dtype in ("bfloat16", "float32")],
    ids=lambda p: "-".join(map(str, p)))
def both(request):
    """The op under the kernels' plan and the float32 reference, each
    (outputs, gradients), with the case."""
    name, taps, dtype = request.param
    length = LENGTHS[name]
    qkv, w, cot = _inputs(length.T, taps, jnp.dtype(dtype))
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("PADDLE_PALLAS_FORCE", "1")
        mp.setattr(kernels, "_BLOCK_T", length.block_t)
        mp.setattr(kernels, "_ROWS", length.rows)
        before = pallas.selections().get(f"causal_conv.{length.impl}", 0)
        got = _run(lambda x, w: gated_causal_conv(
            x, w, n_qk=N_QK, head=HEAD), qkv, w, cot)
        assert pallas.selections()[f"causal_conv.{length.impl}"] \
            == before + 1
    f32 = lambda x: x.astype(jnp.float32)  # noqa: E731
    want = _run(lambda x, w: conv_module._reference(x, w, N_QK, HEAD),
                f32(qkv), f32(w), tuple(map(f32, cot)))
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


@pytest.mark.parametrize("kind", KINDS)
def test_the_outputs_equal_the_reference(both, kind):
    (got, _), (want, _), length, tol = both
    assert got[kind].shape == (B, length.T, N_V if kind == "v" else N_QK)
    _close(got[kind], want[kind], tol)


def test_q_and_k_are_unit_heads(both):
    (got, _), _, length, tol = both
    for kind, norm in (("q", HEAD ** -0.5), ("k", 1.0)):
        heads = got[kind][[0, 2]].reshape(2, length.T, -1, HEAD)
        np.testing.assert_allclose(np.linalg.norm(heads, axis=-1), norm,
                                   rtol=max(tol, 1e-4))


def test_the_gradient_of_qkv_equals_the_reference(both):
    (_, got), (_, want), _, tol = both
    _close(got["qkv"], want["qkv"], tol)


def test_the_gradient_of_the_taps_equals_the_reference(both):
    (_, got), (_, want), length, tol = both
    assert got["conv_w"].shape == want["conv_w"].shape
    _close(got["conv_w"], want["conv_w"], tol, sums=B * length.T)


def test_the_first_rows_have_no_history(both):
    """Rows 0 .. taps - 2 see fewer taps than there are: nothing stands
    in for ``s_{<0}`` (the halo block there holds the tile's own rows)."""
    (got, got_g), (want, want_g), _, tol = both
    taps = want_g["conv_w"].shape[0]
    for kind in KINDS:
        _close(got[kind][:, :taps - 1], want[kind][:, :taps - 1], tol)
    _close(got_g["qkv"][:, :taps - 1], want_g["qkv"][:, :taps - 1], tol)


def test_the_rows_at_every_tile_edge(both):
    """Three rows either side of every tile's and chunk's edge: the
    forward reads across it backwards (the halo, the loop's carry), the
    backward forwards (the carried ``dc``)."""
    (got, got_g), (want, want_g), length, tol = both
    edges = range(length.rows, length.T, length.rows)
    rows = np.concatenate([np.arange(e - 3, e + 3) for e in edges])
    for kind in KINDS:
        _close(got[kind][:, rows], want[kind][:, rows], tol)
    _close(got_g["qkv"][:, rows], want_g["qkv"][:, rows], tol)


def test_nothing_crosses_batch_rows(both):
    """The middle row is nought in and under a nought cotangent: its
    outputs and its gradient are nought whatever its neighbours hold."""
    (got, got_g), _, _, _ = both
    for kind in KINDS:
        assert not np.any(got[kind][1])
    assert not np.any(got_g["qkv"][1])
    assert np.any(got_g["qkv"][0]) and np.any(got_g["qkv"][2])


# ---------------------------------------------------------------------------
# the plan: what it takes, what it refuses, and what is counted
# ---------------------------------------------------------------------------
def _plan(T=64, n_qk=N_QK, n_v=N_V, taps=4, head=HEAD,
          dtype=jnp.bfloat16):
    return kernels.plan(B, T, 2 * n_qk + n_v, taps, head, dtype,
                        n_qk=n_qk, interpret=True)


def test_the_plan_at_the_benchmark_s_size():
    """(4, 8192, 8192), 128-wide heads: tiles of 1024 tokens, column
    blocks of 512 lanes — 4 q blocks, 4 k, 8 v —, chunks of 256 rows."""
    plan = kernels.plan(4, 8192, 8192, 4, 128, jnp.bfloat16, n_qk=2048,
                        interpret=False)
    assert plan == kernels.Plan(kernels._BLOCK_T, kernels._BLOCK_C,
                                kernels._ROWS, False)
    assert 2048 % plan.block_c == 0 and plan.block_t % plan.rows == 0


@pytest.mark.parametrize("why,kw", [
    ("a head that is no whole lane block", dict(head=64)),
    ("q and k that are no whole heads", dict(n_qk=192)),
    ("v that is no whole heads", dict(n_v=320)),
    ("no v at all", dict(n_v=0)),
    ("a T that is no whole 16-row tiles", dict(T=40)),
    ("more history than the carry holds", dict(taps=10)),
    ("a dtype the kernels do not read", dict(dtype=jnp.float16)),
], ids=lambda x: x.replace(" ", "-") if isinstance(x, str) else "")
def test_the_plan_refuses(why, kw):
    assert _plan() is not None
    assert _plan(**kw) is None, why


@pytest.mark.parametrize("n_qk,n_v,block_c", [
    (256, 512, 256), (128, 384, 128), (1024, 512, 512), (256, 256, 256)])
def test_a_column_block_is_whole_heads_of_one_kind(n_qk, n_v, block_c):
    plan = _plan(n_qk=n_qk, n_v=n_v)
    assert plan.block_c == block_c
    assert n_qk % plan.block_c == 0 and n_v % plan.block_c == 0


@pytest.mark.parametrize("force,head,impl", [
    ("1", 128, "interpret"), ("1", 64, "xla"), ("0", 128, "xla")],
    ids=["forced", "forced-but-refused", "off-the-tpu"])
def test_the_selection_is_counted(monkeypatch, force, head, impl):
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", force)
    qkv = jnp.zeros((1, 32, 4 * head), jnp.float32)
    w = jnp.zeros((4, 4 * head), jnp.float32)
    before = pallas.selections()
    q, k, v = jax.eval_shape(lambda x, w: gated_causal_conv(
        x, w, n_qk=head, head=head), qkv, w)
    assert (q.shape, k.shape, v.shape) == (
        (1, 32, head), (1, 32, head), (1, 32, 2 * head))
    after = pallas.selections()
    changed = {k: after[k] - before.get(k, 0) for k in after
               if k.startswith("causal_conv.")
               and after[k] != before.get(k, 0)}
    assert changed == {f"causal_conv.{impl}": 1}


def test_a_wider_head_sums_all_its_lane_blocks(monkeypatch):
    """head = 256: the norm's sum runs over both 128-lane blocks."""
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    qkv, w, cot = _inputs(32, 4, jnp.float32, seed=3)
    got = _run(lambda x, w: gated_causal_conv(x, w, n_qk=N_QK, head=256),
               qkv, w, cot)
    want = _run(lambda x, w: conv_module._reference(x, w, N_QK, 256),
                qkv, w, cot)
    for kind in KINDS:
        _close(got[0][kind], want[0][kind], 2e-6)
    _close(got[1]["qkv"], want[1]["qkv"], 2e-6)
    _close(got[1]["conv_w"], want[1]["conv_w"], 2e-6, sums=B * 32)


def test_under_a_mesh_the_kernels_run_per_shard(monkeypatch):
    """B over ``dp`` = 2 ... the taps' gradient is summed over the
    shards."""
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    mesh = Mesh(np.asarray(jax.devices()[:2]), ("dp",))
    ks = jax.random.split(jax.random.PRNGKey(5), 3)
    qkv = jax.random.normal(ks[0], (2, 32, C))
    w = 0.5 * jax.random.normal(ks[1], (4, C))
    cot = jax.random.normal(ks[2], (2, 32, C))

    def loss(mesh):
        def fn(qkv, w):
            q, k, v = gated_causal_conv(qkv, w, n_qk=N_QK, head=HEAD,
                                        mesh=mesh, batch_axes=("dp",))
            return jnp.sum(jnp.concatenate([q, k, v], -1) * cot)
        return jax.jit(jax.value_and_grad(fn, argnums=(0, 1)))(qkv, w)

    (want, want_g), (got, got_g) = loss(None), loss(mesh)
    np.testing.assert_allclose(got, want, rtol=1e-5)
    for a, b in zip(got_g, want_g):
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-5)
