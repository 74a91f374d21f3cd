"""Pallas kernel tests — run the real kernels in interpret mode on CPU.

`_plan` normally routes CPU to the XLA fallback; setting
``PADDLE_PALLAS_FORCE=1`` forces the pallas path with ``interpret=True`` so
the forward (lse-emitting) kernel and both backward kernels
(`_bwd_dq_kernel`, `_bwd_dkv_kernel`) are exercised by CI, compared against
the XLA reference math (reference parity net: the same numpy-oracle
posture as OpTest, ``tests/unittests/op_test.py:277``).
"""
import ast
import functools
import importlib
import math
import os
import pathlib

import numpy as np
import pytest

import jax
import jax.numpy as jnp

fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


@pytest.fixture
def force_pallas(monkeypatch):
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")


def _ref_attention(q, k, v, causal):
    D = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(D)
    if causal:
        Tq, Tk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((Tq, Tk), bool), k=Tk - Tq)
        s = jnp.where(mask, s, -1e30)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("tq,tk", [
    (256, 256), (128, 256),
    # more than one q block in front of an offset: the forward's q blocks
    # of 128 end at columns 384, 512, 640 and the tiled backward's too
    (384, 640),
    # tq >= 512 interpret-mode runs cost seconds each on one CPU core;
    # they gate in the slow tier (run_all_tests.sh --runslow)
    pytest.param(512, 512, marks=pytest.mark.slow),
    pytest.param(1024, 1024, marks=pytest.mark.slow),
    pytest.param(1152, 1152, marks=pytest.mark.slow),
    pytest.param(640, 1280, marks=pytest.mark.slow)])
def test_flash_fwd_bwd_vs_xla(force_pallas, causal, tq, tk):
    rs = np.random.RandomState(0)
    B, H, D = 2, 2, 64
    q = jnp.asarray(rs.rand(B, tq, H, D), jnp.float32)
    k = jnp.asarray(rs.rand(B, tk, H, D), jnp.float32)
    v = jnp.asarray(rs.rand(B, tk, H, D), jnp.float32)
    g = jnp.asarray(rs.rand(B, tq, H, D), jnp.float32)

    out = fa.flash_attention(q, k, v, causal=causal)
    ref = _ref_attention(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    dq, dk, dv = jax.vjp(
        lambda a, b, c: fa.flash_attention(a, b, c, causal=causal),
        q, k, v)[1](g)
    rq, rk, rv = jax.vjp(
        lambda a, b, c: _ref_attention(a, b, c, causal), q, k, v)[1](g)
    np.testing.assert_allclose(np.asarray(dq), np.asarray(rq), atol=5e-5)
    np.testing.assert_allclose(np.asarray(dk), np.asarray(rk), atol=5e-5)
    np.testing.assert_allclose(np.asarray(dv), np.asarray(rv), atol=5e-5)


def test_flash_under_jit(force_pallas):
    rs = np.random.RandomState(1)
    q = jnp.asarray(rs.rand(1, 128, 2, 32), jnp.float32)

    @jax.jit
    def step(q):
        out = fa.flash_attention(q, q, q, causal=True)
        return jnp.sum(out * out)

    gfn = jax.jit(jax.grad(step))
    loss = step(q)
    grad = gfn(q)
    # same numbers as the XLA path (gate off)
    os.environ["PADDLE_PALLAS_FORCE"] = "0"
    ref_loss = jnp.sum(_ref_attention(q, q, q, True) ** 2)
    ref_grad = jax.grad(
        lambda a: jnp.sum(_ref_attention(a, a, a, True) ** 2))(q)
    np.testing.assert_allclose(float(loss), float(ref_loss), rtol=1e-5)
    np.testing.assert_allclose(np.asarray(grad), np.asarray(ref_grad),
                               atol=5e-5)


def test_causal_cross_attention_gated_off(monkeypatch):
    # causal with seq_q > seq_k degenerates (fully-masked rows) — must
    # stay on the XLA path regardless of the force flag
    assert fa._plan("folded", 1, 384, 128, 2, 64, 4, True).name == "xla"
    plan = fa._plan("folded", 1, 128, 384, 2, 64, 4, True)  # decode shape: ok
    assert plan.name == ("xla" if jax.default_backend() == "cpu"
                         else "small")
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    assert fa._plan("folded", 1, 384, 128, 2, 64, 4, True).name == "xla"
    assert not fa._kernels_apply(384, 128, True)
    assert fa._kernels_apply(384, 128, False)
    assert not fa._kernels_apply(100, 128, False)


# layout, B, T, Tk, heads, d, dtype, causal -> selection, forward and backward
# (block_q, key columns, rows a step), vmem_limit.  Regime split: short
# sequences take the full-K-resident kernels, mid sequences the
# q-block-tiled full-K kernels, and anything past MID_T_MAX the
# online-softmax streaming kernels.  The key columns are the stream
# kernels' chunk or, in a causal call of a whole-row kernel with more than
# one q block, the granule each q block's extent is rounded up to
_PLAN_TABLE = [
    # the two cells: gpt2-medium.train-t1024, lfm2-24b-a2b.train-t8192
    ("stacked", 32, 1024, 1024, 16, 64, "bfloat16", True,
     "packed_mid", (512, 512, 1), (512, 512, 1), None),
    ("folded", 4, 8192, 8192, 32, 64, "bfloat16", True,
     "stream_resident", (1024, 1024, 1), (512, 512, 1), 44564480),
    # stacked: whole rows to 512; bf16 takes q blocks of 512 to T = 1024
    # and 256 past it (the largest that divides T), f32 128 and 32; 4
    # heads of 32 fill a column block as 2 of 64 do.  One q block, or a
    # call that is not causal, has no granule: its rows are taken whole;
    # 64 q blocks of 32 share 8 extents
    ("stacked", 8, 512, 512, 12, 64, "bfloat16", True,
     "packed_small", (512, None, 4), (None, None, 1), None),
    ("stacked", 8, 256, 256, 12, 64, "float32", True,
     "packed_small", (128, 128, 4), (None, None, 2), None),
    ("stacked", 6, 128, 128, 2, 128, "float32", True,
     "packed_small", (128, None, 2), (None, None, 2), None),
    ("stacked", 2, 1024, 1024, 12, 64, "float32", True,
     "packed_mid", (128, 128, 1), (128, 128, 1), None),
    ("stacked", 2, 2048, 2048, 12, 64, "float32", True,
     "packed_mid", (32, 256, 1), (32, 256, 1), None),
    ("stacked", 2, 2048, 2048, 8, 32, "bfloat16", True,
     "packed_mid", (256, 256, 1), (256, 256, 1), None),
    ("stacked", 1, 640, 640, 4, 64, "float32", True,
     "packed_mid", (128, 128, 1), (128, 128, 1), None),
    ("stacked", 1, 768, 768, 4, 64, "bfloat16", True,
     "packed_mid", (256, 256, 1), (256, 256, 1), None),
    ("stacked", 32, 1024, 1024, 16, 64, "bfloat16", False,
     "packed_mid", (512, None, 1), (512, None, 1), None),
    ("stacked", 2, 1152, 1152, 2, 64, "float32", True,
     "packed_mid", (32, 256, 1), (32, 256, 1), None),
    # stacked shapes the stacked kernels do not take fall to the folded
    # plan: T = 4096, a head size that fills no column block, a head
    # count that leaves one half full
    ("stacked", 1, 4096, 4096, 12, 64, "bfloat16", True,
     "mid", (256, 512, 1), (64, 512, 1), None),
    ("stacked", 1, 128, 128, 2, 16, "float32", True,
     "small", (128, None, 2), (None, None, 2), None),
    ("stacked", 2, 512, 512, 3, 64, "bfloat16", True,
     "small", (512, None, 2), (None, None, 2), None),
    # folded: small (whole-row backward to Tk = 512, tiled beyond), mid
    ("folded", 128, 512, 512, 12, 64, "bfloat16", True,
     "small", (512, None, 8), (None, None, 2), None),
    ("folded", 2, 256, 512, 2, 64, "float32", True,
     "small", (128, 128, 4), (None, None, 2), None),
    ("folded", 2, 1024, 1024, 2, 64, "bfloat16", True,
     "small", (512, 512, 4), (512, 512, 1), None),
    ("folded", 2, 1152, 1152, 2, 64, "float32", True,
     "mid", (128, 256, 1), (128, 256, 1), None),
    ("folded", 1, 2048, 2048, 2, 64, "bfloat16", True,
     "mid", (256, 256, 2), (256, 256, 1), None),
    ("folded", 1, 4096, 4096, 12, 64, "bfloat16", True,
     "mid", (256, 512, 1), (64, 512, 1), None),
    ("folded", 1, 2048, 128, 16, 64, "bfloat16", False,
     "mid", (512, None, 16), (512, None, 1), None),
    # XLA math: causal with more queries than keys, a length that is no
    # multiple of 128
    ("folded", 1, 2048, 128, 16, 64, "bfloat16", True,
     "xla", (None, None, 1), (None, None, 1), None),
    ("stacked", 1, 100, 100, 2, 64, "float32", False,
     "xla", (None, None, 1), (None, None, 1), None),
    # stream: head size 128 fills the lanes head size 64 pads; lengths
    # that no long block divides; rows whose budget no chip holds
    ("folded", 1, 8192, 8192, 4, 128, "bfloat16", True,
     "stream_resident", (1024, 1024, 1), (512, 512, 1), 44564480),
    ("folded", 1, 4224, 4608, 4, 64, "bfloat16", True,
     "stream_resident", (128, 512, 1), (128, 512, 1), 20971520),
    ("folded", 1, 65536, 65536, 1, 128, "bfloat16", True,
     "stream", (256, 512, 1), (256, 256, 1), None),
    ("folded", 1, 8320, 8320, 1, 128, "float32", True,
     "stream", (128, 128, 1), (128, 128, 1), None),
]


@pytest.mark.parametrize(
    "layout,B,T,Tk,heads,d,dtype,causal,name,fwd,bwd,vmem_limit", _PLAN_TABLE)
def test_plan_table(force_pallas, layout, B, T, Tk, heads, d, dtype, causal,
                    name, fwd, bwd, vmem_limit):
    plan = fa._plan(layout, B, T, Tk, heads, d, jnp.dtype(dtype).itemsize,
                    causal)
    interpret = name != "xla" and jax.default_backend() != "tpu"
    assert plan == fa._Plan(name, interpret, fwd, bwd, vmem_limit)
    hash(plan)                  # a custom_vjp's static argument


def _traced_kernels(fn, *args):
    """Number of pallas calls in fn's forward and vjp, traced only."""
    def fwd_bwd(*args):
        out, vjp = jax.vjp(fn, *args)
        return vjp(out)
    return str(jax.make_jaxpr(fwd_bwd)(*args)).count("pallas_call")


@pytest.mark.parametrize("regime,layout,T,kernels", [
    ("packed_small", "stacked", 256, 2), ("packed_mid", "stacked", 640, 2),
    ("small", "folded", 256, 2), ("mid", "folded", 1152, 2),
    ("stream_resident", "folded", 8192, 2), ("stream", "folded", 8192, 3),
    ("xla", "folded", 100, 0)])
def test_backward_runs_under_the_forwards_plan(force_pallas, monkeypatch,
                                               regime, layout, T, kernels):
    """One ``_plan`` a public call, none from a vjp rule: the backward
    reads the record its forward ran under."""
    plans = []
    make = fa._plan
    monkeypatch.setattr(fa, "_plan",
                        lambda *a: plans.append(make(*a)) or plans[-1])
    if regime == "stream":      # a chip too small for the resident pair
        monkeypatch.setattr(fa, "_vmem_capacity", lambda: 16 << 20)
    S = jax.ShapeDtypeStruct
    if layout == "stacked":
        n = _traced_kernels(
            lambda x: fa.flash_attention_stacked(x, 2, causal=True),
            S((3, 1, T, 128), jnp.float32))
    else:
        n = _traced_kernels(
            lambda q, k, v: fa.flash_attention(q, k, v, causal=True),
            *[S((1, T, 2, 64), jnp.bfloat16)] * 3)
    assert [p.name for p in plans] == [regime]
    assert n == kernels


@pytest.mark.parametrize("causal", [False, True])
def test_flash_bf16_no_fp32_fallback(force_pallas, causal):
    # the AMP train step feeds the kernel bf16 q/k/v: operands must
    # STAY bf16 through forward and backward (fp32 lives only in the
    # kernel's softmax/accumulator scratch), tracking the fp32
    # reference at bf16 tolerance
    rs = np.random.RandomState(5)
    B, T, H, D = 2, 256, 2, 64
    mk = lambda: jnp.asarray(rs.rand(B, T, H, D), jnp.float32)  # noqa: E731
    q32, k32, v32, g32 = mk(), mk(), mk(), mk()
    q, k, v, g = (a.astype(jnp.bfloat16) for a in (q32, k32, v32, g32))

    out = fa.flash_attention(q, k, v, causal=causal)
    assert out.dtype == jnp.bfloat16
    ref = _ref_attention(q32, k32, v32, causal)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref), atol=3e-2)

    grads = jax.vjp(
        lambda a, b, c: fa.flash_attention(a, b, c, causal=causal),
        q, k, v)[1](g)
    refs = jax.vjp(
        lambda a, b, c: _ref_attention(a, b, c, causal),
        q32, k32, v32)[1](g32)
    for d, r in zip(grads, refs):
        assert d.dtype == jnp.bfloat16
        np.testing.assert_allclose(np.asarray(d, np.float32),
                                   np.asarray(r), atol=5e-2)


def _packed(qkv, H, causal):
    """The stacked entry on a batch-first (B, T, 3*H*d) projection output."""
    B, T, F = qkv.shape
    return fa.flash_attention_stacked(
        jnp.moveaxis(qkv.reshape(B, T, 3, F // 3), 2, 0), H, causal=causal)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("H,D", [
    (4, 64), (2, 128),
    # the P=4 packing regime (8 heads of d=32) is the slowest interpret
    # run of the three — slow tier keeps it gating without the tier-1 cost
    pytest.param(8, 32, marks=pytest.mark.slow)])
def test_flash_attention_packed_layout(force_pallas, causal, H, D):
    # a (B, T, 3, H*d) projection output with its q/k/v axis moved to
    # the front: same numbers as split + generic,
    # across the head-packing regimes (P = 128//d heads per column
    # block: 2 at d=64, 4 at d=32, 1 at d=128)
    rs = np.random.RandomState(3)
    B, T = 2, 256
    qkv = jnp.asarray(rs.rand(B, T, 3 * H * D), jnp.float32)
    out = _packed(qkv, H, causal)
    q, k, v = jnp.split(qkv.reshape(B, T, 3 * H, D), 3, axis=2)
    ref = _ref_attention(q, k, v, causal).reshape(B, T, H * D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)

    g = jnp.asarray(rs.rand(B, T, H * D), jnp.float32)
    dqkv = jax.vjp(lambda a: _packed(a, H, causal),
                   qkv)[1](g)[0]
    ref_d = jax.vjp(
        lambda a: _ref_attention(
            *jnp.split(a.reshape(B, T, 3 * H, D), 3, axis=2),
            causal).reshape(B, T, H * D), qkv)[1](g)[0]
    np.testing.assert_allclose(np.asarray(dqkv), np.asarray(ref_d),
                               atol=5e-5)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("H,D", [(4, 64), (2, 128)])
@pytest.mark.parametrize("B,T,regime", [(2, 256, "packed_small"),
                                        (1, 640, "packed_mid")])
def test_flash_attention_stacked(force_pallas, B, T, regime, H, D, causal):
    # the entry the GPT block calls: q, k, v as the sections of one
    # (3, B, T, H*d) array in, the cotangent in the same form out —
    # forward and vjp against XLA math in the small (whole rows) and mid
    # (q blocks, the output block resident across them) regimes
    from paddle_tpu.ops import pallas
    rs = np.random.RandomState(17)
    qkv = jnp.asarray(rs.rand(3, B, T, H * D), jnp.float32)
    g = jnp.asarray(rs.rand(B, T, H * D), jnp.float32)

    def ref_fn(x):
        q, k, v = (x[i].reshape(B, T, H, D) for i in range(3))
        return _ref_attention(q, k, v, causal).reshape(B, T, H * D)

    before = pallas.selections().get(f"flash_attention.{regime}.interpret", 0)
    out, vjp = jax.vjp(
        lambda x: fa.flash_attention_stacked(x, H, causal=causal), qkv)
    assert pallas.selections()[
        f"flash_attention.{regime}.interpret"] == before + 1
    ref, ref_vjp = jax.vjp(ref_fn, qkv)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    (dqkv,), (ref_d,) = vjp(g), ref_vjp(g)
    assert dqkv.shape == qkv.shape
    np.testing.assert_allclose(np.asarray(dqkv), np.asarray(ref_d),
                               atol=5e-5)


def test_flash_attention_stacked_falls_back_to_split(force_pallas):
    # a head size that fills no 128-lane column block takes the split +
    # generic path
    from paddle_tpu.ops import pallas
    rs = np.random.RandomState(19)
    B, T, H, D = 1, 128, 2, 16
    qkv = jnp.asarray(rs.rand(3, B, T, H * D), jnp.float32)
    before = pallas.selections().get("flash_attention.small.interpret", 0)
    out = fa.flash_attention_stacked(qkv, H, causal=True)
    assert pallas.selections()[
        "flash_attention.small.interpret"] == before + 1
    ref = _ref_attention(*(qkv[i].reshape(B, T, H, D) for i in range(3)),
                         True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.reshape(B, T, H * D)),
                               atol=2e-5)


# ---------------------------------------------------------------------------
# the causal extents of the whole-row kernels: q block qi attends over the
# key columns up to its diagonal, rounded up to the plan's granule — one
# kernel body a distinct extent, the mask on the tile the diagonal crosses
# ---------------------------------------------------------------------------
def test_causal_extents_against_the_mask():
    """For every q block of a grid of (block_q, granule, offset): no
    column from its extent on is seen by any of its rows, every column
    in front of ``start`` is seen by all of them, ``start`` is whole
    128-lane tiles, and the bodies are contiguous runs of q blocks."""
    checked = 0
    for block_q, nq in ((128, 5), (256, 4), (512, 2), (32, 36), (64, 64)):
        for offset in (0, 128, 256, 640):
            T = block_q * nq
            Tk = T + offset
            for granule in {fa._granule(T, Tk, block_q, True),
                            math.lcm(block_q, 128), 2 * math.lcm(block_q,
                                                                  128)}:
                mask = np.tril(np.ones((T, Tk), bool), k=offset)
                extents = fa._causal_extents(block_q, Tk, offset, granule)
                assert [lo for lo, *_ in extents] == \
                    [0] + [hi + 1 for _, hi, *_ in extents[:-1]]
                assert extents[-1][1:3] == (nq - 1, Tk)
                for lo, hi, extent, start in extents:
                    rows = mask[lo * block_q:(hi + 1) * block_q]
                    assert start % 128 == 0 and 0 <= start < extent <= Tk
                    assert extent % granule == 0 or extent == Tk
                    assert not rows[:, extent:].any()
                    assert rows[:, :start].all()
                    # no body is longer than its granule asks for
                    assert rows[:, max(0, extent - granule):extent].any()
                    checked += 1
    assert checked > 200
    # what the plan records keeps a kernel to 8 bodies
    for block_q, T, Tk in ((32, 2048, 2048), (64, 4096, 4096),
                           (128, 1152, 1152), (512, 1024, 1024),
                           (128, 1024, 4096), (32, 1152, 2048)):
        granule = fa._granule(T, Tk, block_q, True)
        assert granule % block_q == 0 and granule % 128 == 0
        assert len(fa._causal_extents(block_q, Tk, Tk - T, granule)) <= 8
    assert fa._granule(1024, 1024, 512, False) is None      # not causal
    assert fa._granule(512, 512, 512, True) is None         # one q block
    assert fa._causal_extents(512, 1024, 0, 512) == \
        [(0, 0, 512, 0), (1, 1, 1024, 512)]                 # the GPT cell's


def _stacked_case(B, T, H, D, dtype, seed=23):
    rs = np.random.RandomState(seed)
    qkv = jnp.asarray(rs.rand(3, B, T, H * D), jnp.float32)
    g = jnp.asarray(rs.rand(B, T, H * D), jnp.float32)

    def ref_fn(x):
        q, k, v = (x[i].reshape(B, T, H, D) for i in range(3))
        return _ref_attention(q, k, v, True).reshape(B, T, H * D)

    return qkv, g, ref_fn


# T, H, D, dtype -> block_q, distinct extents: where the extent logic has
# its edges.  640: five q blocks of 128.  1024 in bf16: the GPT cell's two
# blocks of 512; in f32 eight of 128.  2048 in f32: 64 q blocks of 32
# share 8 extents, so a body's mask tile spans 8 q blocks
@pytest.mark.parametrize("T,H,D,dtype,block_q,bodies", [
    (640, 2, 64, "float32", 128, 5), (640, 1, 128, "float32", 128, 5),
    (1024, 2, 64, "bfloat16", 512, 2), (1024, 1, 128, "bfloat16", 512, 2),
    (1024, 2, 64, "float32", 128, 8), (1024, 1, 128, "float32", 128, 8),
    (2048, 2, 64, "float32", 32, 8), (2048, 1, 128, "float32", 32, 8),
    (768, 2, 64, "bfloat16", 256, 3)])
def test_packed_mid_causal_extents_vs_xla(force_pallas, T, H, D, dtype,
                                          block_q, bodies):
    """Forward and dqkv of the stacked entry against XLA math where each
    q block stops at its own extent."""
    dt = jnp.dtype(dtype)
    plan = fa._plan("stacked", 1, T, T, H, D, dt.itemsize, True)
    assert plan.name == "packed_mid" and plan.fwd[0] == block_q
    assert len(fa._causal_extents(block_q, T, 0, plan.fwd[1])) == bodies
    qkv, g, ref_fn = _stacked_case(1, T, H, D, dtype)
    out, vjp = jax.vjp(
        lambda x: fa.flash_attention_stacked(x, H, causal=True),
        qkv.astype(dt))
    (dqkv,) = vjp(g.astype(dt))
    ref, ref_vjp = jax.vjp(ref_fn, qkv)
    (ref_d,) = ref_vjp(g)
    assert out.dtype == dt and dqkv.dtype == dt
    fwd_tol, bwd_tol = (2e-5, 5e-5) if dt == jnp.float32 else (3e-2, 5e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32), np.asarray(ref),
                               atol=fwd_tol)
    np.testing.assert_allclose(np.asarray(dqkv, np.float32),
                               np.asarray(ref_d), atol=bwd_tol)


def _nt_dot_elements(jaxpr, found):
    """Output elements of every ``a @ b.T`` in a jaxpr and the jaxprs
    under it (a kernel's QK^T — and, in a backward, dO V^T — products),
    by output shape."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "dot_general" and \
                eqn.params["dimension_numbers"] == fa.NT:
            shape = eqn.outvars[0].aval.shape
            found[shape] = found.get(shape, 0) + 1
        for sub in jax.core.jaxprs_in_params(eqn.params):
            _nt_dot_elements(sub, found)
    return found


@pytest.mark.parametrize("causal,live", [(True, 12 / 16), (False, 1.0)])
def test_packed_mid_multiplies_only_the_live_score_columns(force_pallas,
                                                           causal, live):
    """The engagement, counted from the traced kernels at the GPT cell's
    shape: the score elements the QK^T products of the q-block bodies
    make are 12 / 16 of ``nq * block_q * T`` (block_q 512: 512 + 1024 of
    2 x 1024 columns; 10 / 16 at block_q 256) in a causal call, and all
    of them where nothing is masked.  Forward: one such product a head;
    backward: two (scores and dP)."""
    T, H, D = 1024, 2, 64
    S = jax.ShapeDtypeStruct

    def fwd_bwd(x):
        out, vjp = jax.vjp(
            lambda x: fa.flash_attention_stacked(x, H, causal=causal), x)
        return vjp(out)

    calls = [e for e in jax.make_jaxpr(fwd_bwd)(
        S((3, 1, T, H * D), jnp.bfloat16)).jaxpr.eqns
        if e.primitive.name == "pallas_call"]
    assert len(calls) == 2
    plan = fa._plan("stacked", 1, T, T, H, D, 2, causal)
    block_q = plan.fwd[0]
    nq = T // block_q
    for call, per_head in zip(calls, (1, 2)):
        shapes = _nt_dot_elements(call.params["jaxpr"], {})
        assert all(n == per_head * H for n in shapes.values()), shapes
        assert all(rows == block_q for rows, _ in shapes)
        # a causal body runs for one q block, the one unmasked body for all
        runs = 1 if causal else nq
        elements = sum(rows * cols for rows, cols in shapes) * runs
        assert elements == live * nq * block_q * T
    # the same count at the block the kernels had to PR 33
    tiles = (256, fa._granule(T, T, 256, causal), 1)
    plan = plan._replace(fwd=tiles, bwd=tiles)
    jaxpr = jax.make_jaxpr(
        lambda x: fa._qkv_fwd(x, H, 0.125, causal, plan))(
        S((3, 1, T, H * D), jnp.bfloat16)).jaxpr
    shapes = _nt_dot_elements(jaxpr, {})
    elements = sum(r * c for r, c in shapes) * (1 if causal else 4)
    assert elements == (10 / 16 if causal else 1.0) * 4 * 256 * T


def test_layers_share_one_trace_of_the_stacked_kernels(force_pallas):
    """A model's unrolled layer loop calls the stacked entry once a layer
    with the same shapes: every layer's forward and backward call carry
    the kernel jaxpr the first layer traced (``traced_once``), so set-up
    pays for two kernel bodies and not for two a layer."""
    def layers(x):
        for _ in range(3):
            y, vjp = jax.vjp(
                lambda x: fa.flash_attention_stacked(x, 2, causal=True), x)
            x = x + vjp(y)[0]
        return x

    calls = [e for e in jax.make_jaxpr(layers)(
        jax.ShapeDtypeStruct((3, 1, 640, 128), jnp.float32)).jaxpr.eqns
        if e.primitive.name == "pallas_call"]
    assert len(calls) == 6
    assert len({id(e.params["jaxpr"]) for e in calls}) == 2


@pytest.mark.slow
def test_packed_mid_qkv_t1024_gradient(force_pallas):
    """Pins the packed mid-regime entry (512 < T <= 2048): attention
    straight from the (B, T, 3F) projection output with the q-block-
    tiled backward accumulating dK/dV per 128-lane column block —
    forward and dqkv must match the split + XLA reference."""
    rs = np.random.RandomState(11)
    B, T, H, D = 1, 1024, 2, 64
    qkv = jnp.asarray(rs.rand(B, T, 3 * H * D), jnp.float32)
    out = _packed(qkv, H, True)
    q, k, v = jnp.split(qkv.reshape(B, T, 3 * H, D), 3, axis=2)
    ref = _ref_attention(q, k, v, True).reshape(B, T, H * D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)
    g = jnp.asarray(rs.rand(B, T, H * D), jnp.float32)
    dqkv = jax.vjp(lambda a: _packed(a, H, True),
                   qkv)[1](g)[0]
    ref_d = jax.vjp(
        lambda a: _ref_attention(
            *jnp.split(a.reshape(B, T, 3 * H, D), 3, axis=2),
            True).reshape(B, T, H * D), qkv)[1](g)[0]
    np.testing.assert_allclose(np.asarray(dqkv), np.asarray(ref_d),
                               atol=5e-5)


@pytest.mark.slow
@pytest.mark.parametrize("T,H,D", [(768, 4, 32), (2048, 2, 64)])
def test_packed_mid_qkv_more_shapes(force_pallas, T, H, D):
    """Packed mid entry across head-packing regimes and at the 2048
    boundary (where the f32 VMEM budget halves block_q)."""
    rs = np.random.RandomState(13)
    B = 1
    qkv = jnp.asarray(rs.rand(B, T, 3 * H * D), jnp.float32)
    out = _packed(qkv, H, True)
    q, k, v = jnp.split(qkv.reshape(B, T, 3 * H, D), 3, axis=2)
    ref = _ref_attention(q, k, v, True).reshape(B, T, H * D)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               atol=2e-5)
    g = jnp.asarray(rs.rand(B, T, H * D), jnp.float32)
    dqkv = jax.vjp(lambda a: _packed(a, H, True),
                   qkv)[1](g)[0]
    ref_d = jax.vjp(
        lambda a: _ref_attention(
            *jnp.split(a.reshape(B, T, 3 * H, D), 3, axis=2),
            True).reshape(B, T, H * D), qkv)[1](g)[0]
    np.testing.assert_allclose(np.asarray(dqkv), np.asarray(ref_d),
                               atol=5e-5)


@pytest.mark.slow
def test_mid_regime_t2048_gradient(force_pallas):
    """Pins the long-context (mid-regime) kernel pair at T=2048: the
    full-K-resident tiled forward/backward must match XLA math — this
    is the per-shard primitive ring attention composes over (round-5
    verdict item 2)."""
    rs = np.random.RandomState(7)
    B, T, H, D = 1, 2048, 2, 64
    q = jnp.asarray(rs.rand(B, T, H, D), jnp.float32)
    k = jnp.asarray(rs.rand(B, T, H, D), jnp.float32)
    v = jnp.asarray(rs.rand(B, T, H, D), jnp.float32)
    g = jnp.asarray(rs.rand(B, T, H, D), jnp.float32)
    assert fa._plan("folded", B, T, T, H, D, 4, True).name == "mid"
    for causal in (False, True):
        out, vjp = jax.vjp(
            lambda a, b, c: fa.flash_attention(a, b, c, causal=causal),
            q, k, v)
        ref, rvjp = jax.vjp(
            lambda a, b, c: _ref_attention(a, b, c, causal), q, k, v)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   atol=2e-5)
        for got, want in zip(vjp(g), rvjp(g)):
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       atol=5e-5)


class TestSoftmaxXentHead:
    """Fused LM loss head (ops/pallas/softmax_xent.py) vs the jnp
    reference, in interpret mode — the kernels that replace chunked_ce
    on TPU for the flagship (round-5)."""

    @staticmethod
    def _ref(x, w, lab):
        logits = (x @ w).astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        at = jnp.take_along_axis(logits, lab[:, None], 1)[:, 0]
        return jnp.mean(lse - at)

    @pytest.mark.parametrize("V", [512, 700, 1000])
    def test_loss_and_grads_match_reference(self, V):
        # V=700/1000 exercise the lane-tile vocab padding (V % 512 != 0)
        from paddle_tpu.ops.pallas import softmax_xent as sx
        rs = np.random.RandomState(0)
        N, D = 256, 64
        x = jnp.asarray(rs.randn(N, D), jnp.float32)
        w = jnp.asarray(rs.randn(D, V) * 0.05, jnp.float32)
        lab = jnp.asarray(rs.randint(0, V, (N,)), jnp.int32)
        loss = sx.softmax_xent_loss(x, w, lab, True)
        np.testing.assert_allclose(float(loss), float(self._ref(x, w, lab)),
                                   rtol=1e-6)
        got = jax.grad(lambda x, w: sx.softmax_xent_loss(x, w, lab, True),
                       (0, 1))(x, w)
        want = jax.grad(lambda x, w: self._ref(x, w, lab), (0, 1))(x, w)
        for a, b in zip(got, want):
            np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                       atol=2e-6)

    def test_fwd_kernel_outputs(self):
        from paddle_tpu.ops.pallas import softmax_xent as sx
        rs = np.random.RandomState(1)
        N, D, V = 128, 32, 384
        x = jnp.asarray(rs.randn(N, D), jnp.float32)
        w = jnp.asarray(rs.randn(D, V) * 0.1, jnp.float32)
        lab = jnp.asarray(rs.randint(0, V, (N,)), jnp.int32)
        lse, at = sx.softmax_xent_fwd(x, w, lab, interpret=True)
        logits = x @ w
        np.testing.assert_allclose(
            np.asarray(lse),
            np.asarray(jax.scipy.special.logsumexp(logits, -1)), atol=1e-5)
        np.testing.assert_allclose(
            np.asarray(at),
            np.asarray(jnp.take_along_axis(logits, lab[:, None], 1)[:, 0]),
            atol=1e-5)

    def test_bf16_inputs(self):
        from paddle_tpu.ops.pallas import softmax_xent as sx
        rs = np.random.RandomState(2)
        N, D, V = 128, 32, 512
        x = jnp.asarray(rs.randn(N, D), jnp.bfloat16)
        w = jnp.asarray(rs.randn(D, V) * 0.05, jnp.bfloat16)
        lab = jnp.asarray(rs.randint(0, V, (N,)), jnp.int32)
        loss = sx.softmax_xent_loss(x, w, lab, True)
        ref = self._ref(x.astype(jnp.float32), w.astype(jnp.float32), lab)
        np.testing.assert_allclose(float(loss), float(ref), rtol=2e-2)
        dx, dw = jax.grad(
            lambda x, w: sx.softmax_xent_loss(x, w, lab, True), (0, 1))(x, w)
        assert dx.dtype == jnp.bfloat16 and dw.dtype == jnp.bfloat16

    def test_dlogits_kernel_matches_softmax(self):
        from paddle_tpu.ops.pallas import softmax_xent as sx
        rs = np.random.RandomState(3)
        N, D, V = 128, 32, 384
        x = jnp.asarray(rs.randn(N, D), jnp.float32)
        w = jnp.asarray(rs.randn(D, V) * 0.1, jnp.float32)
        lab = jnp.asarray(rs.randint(0, V, (N,)), jnp.int32)
        logits = x @ w
        lse = jax.scipy.special.logsumexp(logits, -1)
        dl = sx.softmax_xent_dlogits(x, w, lab, lse, 2.0, interpret=True)
        want = (jax.nn.softmax(logits, -1)
                - jax.nn.one_hot(lab, V)) * 2.0
        Vp = dl.shape[1]
        np.testing.assert_allclose(np.asarray(dl[:, :V]),
                                   np.asarray(want), atol=1e-5)
        if Vp > V:       # pad columns must be exactly zero
            assert not np.asarray(dl[:, V:]).any()


def _stream_plan(form, block_q, chunk):
    """A plan for a direct call of the stream regime's launchers, in
    interpret mode, at blocks of the test's choosing."""
    blocks = (block_q, chunk, 1)
    return fa._Plan("stream_resident" if form == "resident" else "stream",
                    True, fwd=blocks, bwd=blocks)


def _stream_forwards():
    """The stream regime's two forwards, at blocks small enough for a
    256-row call to take several of each."""
    return {form: functools.partial(fa._stream_flash_fwd,
                                    plan=_stream_plan(form, 128, 128))
            for form in ("grid", "resident")}


@pytest.mark.parametrize("form", ["grid", "resident"])
def test_lse_matches_logsumexp(force_pallas, form):
    rs = np.random.RandomState(2)
    BH, T, D = 2, 256, 32
    q = jnp.asarray(rs.rand(BH, T, D), jnp.float32)
    k = jnp.asarray(rs.rand(BH, T, D), jnp.float32)
    v = jnp.asarray(rs.rand(BH, T, D), jnp.float32)
    scale = 1.0 / np.sqrt(D)
    _, lse = _stream_forwards()[form](q, k, v, scale, False)
    s = jnp.einsum("bqd,bkd->bqk", q, k) * scale
    ref = jax.scipy.special.logsumexp(s, axis=-1)[..., None]
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref), atol=1e-5)


# ---------------------------------------------------------------------------
# the stream regime's kernels, called directly: the resident pair (K/V
# rows in VMEM, a loop over the live key chunks, one fused backward) and
# the grid-streamed pair with its clamped index maps
# ---------------------------------------------------------------------------
def _stream_cases():
    cases = []
    # every block shape x causal x decode offset, at the LFM2 head size:
    # block_q / chunk puts 1, 2 and 4 chunks on the diagonal
    for form, bq, ck in (("resident", 128, 128), ("resident", 256, 128),
                         ("resident", 512, 128), ("resident", 128, 256),
                         ("grid", 128, 128), ("grid", 256, 128)):
        for causal in (True, False):
            for tq, tk in ((512, 512), (256, 512)):
                if bq <= tq:
                    cases.append((form, bq, ck, causal, tq, tk, 64,
                                  "float32"))
    # head size and dtype, where the mask works hardest
    for d, dtype in ((128, "float32"), (64, "bfloat16"), (128, "bfloat16")):
        for causal in (True, False):
            for tq, tk in ((256, 256), (128, 384)):
                cases.append(("resident", 128, 128, causal, tq, tk, d,
                              dtype))
    return cases


@pytest.mark.parametrize("form,bq,ck,causal,tq,tk,d,dtype", _stream_cases())
def test_stream_kernels_vs_xla(form, bq, ck, causal, tq, tk, d, dtype):
    """out, lse, dq, dk, dv of each stream-regime pair against the XLA
    math and its ``jax.vjp``."""
    rs = np.random.RandomState(3)
    dt = jnp.dtype(dtype)
    q, g = (jnp.asarray(rs.randn(1, tq, d), dt) for _ in range(2))
    k, v = (jnp.asarray(rs.randn(1, tk, d), dt) for _ in range(2))
    scale = 1.0 / np.sqrt(d)
    plan = _stream_plan(form, bq, ck)
    bwd = fa._resident_flash_bwd if form == "resident" else fa._flash_bwd
    out, lse = fa._stream_flash_fwd(q, k, v, scale, causal, plan)
    grads = bwd(q, k, v, out, lse, g, scale, causal, plan)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref, vjp = jax.vjp(
        lambda a, b, c: fa._xla_attention(a, b, c, scale, causal), *f32)
    s = jnp.einsum("bqd,bkd->bqk", f32[0], f32[1]) * scale
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((tq, tk), bool), k=tk - tq), s,
                      -jnp.inf)
    want_lse = jax.scipy.special.logsumexp(s, axis=-1)[..., None]
    tol = 3e-5 if dt == jnp.float32 else 4e-2
    assert out.dtype == dt and lse.dtype == jnp.float32
    assert lse.shape == (1, tq, 1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(want_lse),
                               atol=3e-5 if dt == jnp.float32 else 2e-2)
    for name, got, want in zip(("out", "dq", "dk", "dv"), (out, *grads),
                               (ref, *vjp(g.astype(jnp.float32)))):
        assert got.dtype == dt, name
        np.testing.assert_allclose(
            np.asarray(got.astype(jnp.float32)), np.asarray(want),
            atol=tol, rtol=tol, err_msg=name)


def test_live_chunks_against_the_mask():
    """The loop bounds of the resident kernels for every q block of a
    small grid of (block_q, chunk, offset): chunks below ``n_full`` hold
    no masked score, chunks from ``n_live`` on hold no live one, and
    every chunk between is crossed by the diagonal."""
    checked = 0
    for block_q in (1, 2, 3, 4, 8):
        for chunk in (1, 2, 3, 4, 8):
            for nq in (1, 2, 5):
                for offset in (0, 1, 3, 8, 9):
                    seq_q = nq * block_q
                    seq_k = seq_q + offset
                    if seq_k % chunk:
                        continue
                    nk = seq_k // chunk
                    mask = np.tril(np.ones((seq_q, seq_k), bool), k=offset)
                    for qi in range(nq):
                        n_lo, n_clean, n_full, n_live = (
                            int(n) for n in fa._live_chunks(
                                qi, block_q, chunk, offset, nk))
                        assert n_lo == n_clean == 0     # no window
                        rows = mask[qi * block_q:(qi + 1) * block_q]
                        tiles = [rows[:, j * chunk:(j + 1) * chunk]
                                 for j in range(nk)]
                        assert 0 <= n_full <= n_live <= nk
                        assert n_live >= 1          # column 0 is live
                        assert all(t.all() for t in tiles[:n_full])
                        assert not any(t.any() for t in tiles[n_live:])
                        assert all(t.any() and not t.all()
                                   for t in tiles[n_full:n_live])
                        checked += 1
    assert checked > 300
    assert fa._live_chunks(3, 4, 2, 0, 7, causal=False) == (0, 0, 7, 7)


@pytest.mark.parametrize("offset", [0, 256])
def test_dead_grid_steps_name_a_live_block(offset):
    """The grid-streamed kernels' index maps: a step the causal mask
    leaves dead names the block of the nearest live step (so nothing is
    fetched for it), a live step names its own."""
    block_q, block_k, nq = 256, 128, 4
    nk = (nq * block_q + offset) // block_k
    k_map = fa._clamped_k_map(block_q, block_k, offset, nk, True)
    q_map = fa._clamped_q_map(block_q, block_k, offset, True)

    def live(i, j):
        return (i + 1) * block_q - 1 + offset >= j * block_k

    for i in range(nq):
        for j in range(nk):
            kj = int(k_map(0, i, j)[1])
            qi = int(q_map(0, j, i)[1])
            assert live(i, kj) and live(qi, j)
            if live(i, j):
                assert (kj, qi) == (j, i)
            else:
                assert kj == max(jj for jj in range(nk) if live(i, jj))
                assert qi == min(ii for ii in range(nq) if live(ii, j))
    plain = fa._clamped_k_map(block_q, block_k, offset, nk, False)
    assert plain(0, 0, nk - 1) == (0, nk - 1, 0)


def test_stream_mode_through_the_public_entry(force_pallas, monkeypatch):
    """``flash_attention`` in the stream mode (the thresholds lowered so
    that 256 rows select it): values and gradients against the XLA math,
    the selection counted under both names, and a remat policy that
    lists ``RESIDUAL_NAMES`` keeps the forward kernel out of the
    backward pass."""
    from paddle_tpu.ops import pallas
    monkeypatch.setattr(fa, "SMALL_T_MAX", 0)
    monkeypatch.setattr(fa, "MID_T_MAX", 0)
    rs = np.random.RandomState(4)
    q, k, v, g = (jnp.asarray(rs.randn(1, 256, 2, 64), jnp.float32)
                  for _ in range(4))
    before = pallas.selections()

    def attend(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)

    out, vjp = jax.vjp(attend, q, k, v)
    ref, ref_vjp = jax.vjp(lambda a, b, c: _ref_attention(a, b, c, True),
                           q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)
    for got, want in zip(vjp(g), ref_vjp(g)):
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   atol=5e-5)
    after = pallas.selections()
    for name in ("flash_attention.stream.interpret",
                 "flash_attention.stream_resident.interpret"):
        assert after.get(name, 0) > before.get(name, 0), name

    def kernels_in_grad(policy):
        loss = jax.checkpoint(lambda q: jnp.sum(attend(q, k, v) * g),
                              policy=policy)
        return str(jax.make_jaxpr(jax.grad(loss))(q)).count("pallas_call")

    policies = jax.checkpoint_policies
    # forward + fused backward; with nothing kept, the forward again
    assert kernels_in_grad(
        policies.save_only_these_names(*fa.RESIDUAL_NAMES)) == 2
    assert kernels_in_grad(policies.nothing_saveable) == 3


def test_resident_pair_is_taken_while_its_vmem_fits(force_pallas,
                                                    monkeypatch):
    """Selection inside the stream regime is by (Tk, d, itemsize) and the
    chip's VMEM alone: the resident pair asks for its budget and a
    quarter more, and hands rows that do not fit to the grid-streamed
    kernels (``test_plan_table`` holds the shapes)."""
    need = fa._resident_vmem_bytes(8192, 64, 2, 512, 512)
    plan = fa._plan("folded", 4, 8192, 8192, 32, 64, 2, True)
    assert plan.vmem_limit == need + need // 4
    assert 24 << 20 < need < plan.vmem_limit <= 0.75 * fa._vmem_capacity()
    # the same shape on a chip with half the VMEM
    monkeypatch.setattr(fa, "_vmem_capacity", lambda: 32 << 20)
    small_chip = fa._plan("folded", 4, 8192, 8192, 32, 64, 2, True)
    assert (small_chip.name, small_chip.vmem_limit) == ("stream", None)


# ---------------------------------------------------------------------------
# fused bias + dropout + residual + layernorm (ops/fused_ops.py)
# ---------------------------------------------------------------------------
class TestFusedBiasDropoutResidualLN:
    def _inputs(self):
        rs = np.random.RandomState(0)
        return (rs.randn(4, 16, 64).astype("float32"),
                rs.randn(4, 16, 64).astype("float32"),
                rs.randn(64).astype("float32"),
                rs.rand(64).astype("float32") + 0.5,
                rs.randn(64).astype("float32"))

    def test_backend_parity_and_math(self, force_pallas):
        import paddle_tpu as paddle
        from paddle_tpu.ops.fused_ops import \
            fused_bias_dropout_residual_layer_norm as fused
        from paddle_tpu.utils import flags
        x, res, b, g, be = self._inputs()
        try:
            # identical seeds -> identical masks across backends (shared
            # counter-based hash RNG), so the flag flip is bit-transparent
            out0 = None
            for p in (0.0, 0.3):
                paddle.seed(42)
                flags.set_flags({"FLAGS_use_pallas": 1})
                o1 = fused(x, res, b, g, be, dropout_rate=p)
                paddle.seed(42)
                flags.set_flags({"FLAGS_use_pallas": 0})
                o2 = fused(x, res, b, g, be, dropout_rate=p)
                np.testing.assert_allclose(o1.numpy(), o2.numpy(), atol=1e-6)
                if p == 0.0:
                    out0 = o2.numpy()
            # p=0 equals the composed reference
            z = res + (x + b)
            zc = z - z.mean(-1, keepdims=True)
            ref = zc / np.sqrt((zc ** 2).mean(-1, keepdims=True) + 1e-5) \
                * g + be
            np.testing.assert_allclose(out0, ref, atol=1e-4)
        finally:
            flags.set_flags({"FLAGS_use_pallas": 1})

    def test_grads(self, force_pallas):
        import paddle_tpu as paddle
        from paddle_tpu.ops.fused_ops import \
            fused_bias_dropout_residual_layer_norm as fused
        x, res, b, g, be = self._inputs()
        paddle.seed(3)
        xt = paddle.to_tensor(x, stop_gradient=False)
        rt = paddle.to_tensor(res, stop_gradient=False)
        gt = paddle.to_tensor(g, stop_gradient=False)
        out = fused(xt, rt, b, gt, be, dropout_rate=0.4)
        paddle.sum(out * out).backward()
        for t in (xt, rt, gt):
            assert t.grad is not None
            assert float(paddle.sum(paddle.abs(t.grad))) > 0
        # p=0 grad vs composed-op autodiff
        paddle.seed(3)
        xt2 = paddle.to_tensor(x, stop_gradient=False)
        out = fused(xt2, res, b, g, be, dropout_rate=0.0)
        paddle.sum(out * out).backward()
        import paddle_tpu.ops as P

        xt3 = paddle.to_tensor(x, stop_gradient=False)
        z = paddle.to_tensor(res) + (xt3 + paddle.to_tensor(b))
        ln = P.layer_norm(z, [64], paddle.to_tensor(g),
                          paddle.to_tensor(be), 1e-5)
        paddle.sum(ln * ln).backward()
        np.testing.assert_allclose(xt2.grad.numpy(), xt3.grad.numpy(),
                                   atol=1e-3)

    def test_layer(self, force_pallas):
        import paddle_tpu as paddle
        layer = paddle.incubate.nn.FusedBiasDropoutResidualLayerNorm(
            32, dropout_rate=0.1)
        x = np.random.RandomState(1).randn(2, 8, 32).astype("float32")
        out = layer(paddle.to_tensor(x), paddle.to_tensor(x))
        assert list(out.shape) == [2, 8, 32]
        layer.eval()
        o1 = layer(paddle.to_tensor(x), paddle.to_tensor(x))
        o2 = layer(paddle.to_tensor(x), paddle.to_tensor(x))
        np.testing.assert_allclose(o1.numpy(), o2.numpy())  # no dropout


def test_sdpa_registry_flip(force_pallas):
    """FLAGS_use_pallas flips scaled_dot_product_attention through the
    dispatch-level registry consultation (core/dispatch.py)."""
    import paddle_tpu as paddle
    from paddle_tpu.utils import flags
    rs = np.random.RandomState(5)
    q = rs.rand(1, 128, 2, 16).astype("float32")
    try:
        flags.set_flags({"FLAGS_use_pallas": 1})
        o1 = paddle.nn.functional.scaled_dot_product_attention(
            q, q, q, is_causal=True)
        flags.set_flags({"FLAGS_use_pallas": 0})
        o2 = paddle.nn.functional.scaled_dot_product_attention(
            q, q, q, is_causal=True)
        np.testing.assert_allclose(o1.numpy(), o2.numpy(), atol=2e-5)
    finally:
        flags.set_flags({"FLAGS_use_pallas": 1})


# ---------------------------------------------------------------------------
# latent attention's heads: q and k of one size, v and the output of another
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("bq,ck,causal,tq,tk,d,dv,dtype", [
    (128, 128, True, 512, 512, 192, 128, "float32"),
    (256, 128, True, 512, 512, 192, 128, "float32"),
    (128, 256, True, 256, 512, 192, 128, "float32"),
    (128, 128, False, 256, 384, 192, 128, "float32"),
    (128, 128, True, 384, 384, 192, 128, "bfloat16"),
    (128, 128, True, 256, 256, 48, 80, "float32"),
], ids=["blocks-128", "q-block-256", "chunk-256-decode-offset",
        "not-causal", "bfloat16", "v-wider-than-q"])
def test_resident_pair_at_unequal_head_sizes_vs_xla(bq, ck, causal, tq, tk,
                                                    d, dv, dtype):
    """The resident pair at q/k head size ``d`` over v head size ``dv``
    (JoyAI-LLM-Flash: 192 over 128), interpreted: out, lse and all three
    gradients against the XLA math and its ``jax.vjp``; T a multiple of
    the blocks and longer than one chunk.  The softmax scale is the
    q/k size's."""
    rs = np.random.RandomState(7)
    dt = jnp.dtype(dtype)
    q = jnp.asarray(rs.randn(2, tq, d), dt)
    k = jnp.asarray(rs.randn(2, tk, d), dt)
    v = jnp.asarray(rs.randn(2, tk, dv), dt)
    g = jnp.asarray(rs.randn(2, tq, dv), dt)
    scale = 1.0 / np.sqrt(d)
    plan = _stream_plan("resident", bq, ck)
    out, lse = fa._stream_flash_fwd(q, k, v, scale, causal, plan)
    grads = fa._resident_flash_bwd(q, k, v, out, lse, g, scale, causal, plan)
    f32 = [x.astype(jnp.float32) for x in (q, k, v)]
    ref, vjp = jax.vjp(
        lambda a, b, c: fa._xla_attention(a, b, c, scale, causal), *f32)
    tol = 3e-5 if dt == jnp.float32 else 4e-2
    assert out.shape == (2, tq, dv) and lse.shape == (2, tq, 1)
    for name, got, want in zip(("out", "dq", "dk", "dv"), (out, *grads),
                               (ref, *vjp(g.astype(jnp.float32)))):
        assert got.dtype == dt and got.shape == want.shape, name
        np.testing.assert_allclose(
            np.asarray(got.astype(jnp.float32)), np.asarray(want),
            atol=tol, rtol=tol, err_msg=name)


def _latent_operands(rs, B, tq, tk, H, dn, dr, dv, dt):
    """Token-major split-key operands and an output cotangent."""
    def x(*shape):
        return jnp.asarray(rs.randn(*shape), dt)
    return (x(B, tq, H * dn), x(B, tq, H * dr), x(B, tk, H * dn),
            x(B, tk, dr), x(B, tk, H * dv)), x(B, tq, H * dv)


def _latent_reference(q_n, q_r, k_n, k_r, v, scale, causal):
    """The XLA math on the concatenated heads: q = [q_n | q_r], k = [k_n |
    k_r repeated over the heads] -> (B, T, H*dv)."""
    B, T, _ = q_n.shape
    Tk, dr = k_r.shape[1:]
    H = q_r.shape[-1] // dr
    q = jnp.concatenate([q_n.reshape(B, T, H, -1),
                         q_r.reshape(B, T, H, dr)], axis=-1)
    k = jnp.concatenate([k_n.reshape(B, Tk, H, -1), jnp.broadcast_to(
        k_r[:, :, None], (B, Tk, H, dr))], axis=-1)
    out = fa._xla_attention(fa._fold(q), fa._fold(k),
                            fa._fold(v.reshape(B, Tk, H, -1)), scale, causal)
    return fa._unfold(out, B).reshape(B, T, -1)


@pytest.mark.parametrize("bq,ck,causal,tq,tk,heads,dtype", [
    (128, 128, True, 512, 512, 2, "float32"),
    (256, 128, True, 512, 512, 2, "float32"),
    (128, 256, True, 256, 512, 2, "float32"),
    (128, 128, False, 256, 384, 2, "float32"),
    (128, 128, True, 384, 384, 2, "bfloat16"),
    (128, 128, True, 256, 256, 4, "bfloat16"),
], ids=["blocks-128", "q-block-256", "chunk-256-decode-offset",
        "not-causal", "bfloat16", "bfloat16-four-heads"])
def test_split_key_pair_vs_xla(bq, ck, causal, tq, tk, heads, dtype):
    """The split-key entry of the resident pair (latent attention, q = [q_n
    | q_r] per head, k = [k_n | the one k_r], the operands token-major),
    interpreted, at the shapes of the test above: out and all five
    gradients against the XLA math on the concatenated heads and its
    ``jax.vjp``.  Two heads, so that the two halves of q_r's 128-lane
    block (heads of 64) are each a head's; four, so that heads 2 and 3
    read the second block."""
    rs = np.random.RandomState(7)
    dt = jnp.dtype(dtype)
    ops, g = _latent_operands(rs, 1, tq, tk, heads, 128, 64, 128, dt)
    scale = 1.0 / np.sqrt(128 + 64)
    plan = _stream_plan("resident", bq, ck)
    out, vjp = jax.vjp(
        lambda *a: fa._flash_latent(*a, scale, causal, plan), *ops)
    grads = vjp(g)
    ref, ref_vjp = jax.vjp(
        lambda *a: _latent_reference(*a, scale, causal),
        *(x.astype(jnp.float32) for x in ops))
    tol = 3e-5 if dt == jnp.float32 else 4e-2
    for name, got, want in zip(
            ("out", "dq_n", "dq_r", "dk_n", "dk_r", "dv"), (out, *grads),
            (ref, *ref_vjp(g.astype(jnp.float32)))):
        assert got.dtype == dt and got.shape == want.shape, name
        np.testing.assert_allclose(
            np.asarray(got.astype(jnp.float32)), np.asarray(want),
            atol=tol, rtol=tol, err_msg=name)


def test_split_keys_through_the_public_entry(force_pallas, monkeypatch):
    """``flash_attention_latent``: where the resident pair is planned (the
    thresholds lowered so that 256 rows select it) the split-key kernels
    run and count as such, values and gradients agree with the XLA math.
    Elsewhere the concatenated heads run as ``flash_attention`` runs
    them: XLA math at a length the whole-row regimes own (they take no
    q/k head of 192 over a v head of 128); an own part that fills no
    whole lane block (64 + 64 over 128) takes the small kernels there
    and the resident pair at 256 rows once the thresholds are lowered."""
    from paddle_tpu.ops import pallas
    rs = np.random.RandomState(8)
    ops, g = _latent_operands(rs, 1, 256, 256, 2, 128, 64, 128,
                              jnp.float32)
    narrow, _ = _latent_operands(rs, 1, 256, 256, 2, 64, 64, 128,
                                 jnp.float32)

    def took(*args):
        before = pallas.selections()
        out, vjp = jax.vjp(
            functools.partial(fa.flash_attention_latent, causal=True),
            *args)
        return out, vjp, {n for n, c in pallas.selections().items()
                          if c != before.get(n, 0)}

    want, ref_vjp = jax.vjp(
        lambda *a: _latent_reference(*a, 192 ** -0.5, True), *ops)
    narrow_want = _latent_reference(*narrow, 128 ** -0.5, True)
    out, _, names = took(*ops)
    assert names == {"flash_attention.xla"}
    np.testing.assert_allclose(out, want, atol=2e-5)
    out, _, names = took(*narrow)
    assert names == {"flash_attention.small.interpret"}
    np.testing.assert_allclose(out, narrow_want, atol=2e-5)
    monkeypatch.setattr(fa, "SMALL_T_MAX", 0)
    monkeypatch.setattr(fa, "MID_T_MAX", 0)
    out, vjp, names = took(*ops)
    assert names == {"flash_attention.stream.interpret",
                     "flash_attention.stream_resident_latent.interpret"}
    assert out.shape == (1, 256, 256)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, exp in zip(vjp(g), ref_vjp(g)):
        np.testing.assert_allclose(got, exp, atol=5e-5)
    out, _, names = took(*narrow)
    assert names == {"flash_attention.stream.interpret",
                     "flash_attention.stream_resident.interpret"}
    np.testing.assert_allclose(out, narrow_want, atol=2e-5)


def test_unequal_head_sizes_through_the_public_entry(force_pallas,
                                                     monkeypatch):
    """``flash_attention`` with a v head narrower than q's: the stream
    regime (thresholds lowered so that 256 rows select it) takes the
    resident pair, values and gradients agree with the XLA math; at a
    length the whole-row regimes own the call is XLA math and counted as
    such."""
    from paddle_tpu.ops import pallas
    rs = np.random.RandomState(8)
    q, k = (jnp.asarray(rs.randn(1, 256, 2, 192), jnp.float32)
            for _ in range(2))
    v, g = (jnp.asarray(rs.randn(1, 256, 2, 128), jnp.float32)
            for _ in range(2))

    def attend(q, k, v):
        return fa.flash_attention(q, k, v, causal=True)

    def ref(q, k, v):
        out = fa._xla_attention(fa._fold(q), fa._fold(k), fa._fold(v),
                                192 ** -0.5, True)
        return fa._unfold(out, 1)

    want, ref_vjp = jax.vjp(ref, q, k, v)
    before = pallas.selections()
    np.testing.assert_allclose(attend(q, k, v), want, atol=2e-5)
    took = {n for n, c in pallas.selections().items()
            if c != before.get(n, 0)}
    assert took == {"flash_attention.xla"}
    monkeypatch.setattr(fa, "SMALL_T_MAX", 0)
    monkeypatch.setattr(fa, "MID_T_MAX", 0)
    before = pallas.selections()
    out, vjp = jax.vjp(attend, q, k, v)
    took = {n for n, c in pallas.selections().items()
            if c != before.get(n, 0)}
    assert took == {"flash_attention.stream.interpret",
                    "flash_attention.stream_resident.interpret"}
    assert out.shape == (1, 256, 2, 128)
    np.testing.assert_allclose(out, want, atol=2e-5)
    for got, exp in zip(vjp(g), ref_vjp(g)):
        np.testing.assert_allclose(got, exp, atol=5e-5)


@pytest.mark.parametrize("T,capacity,name,vmem_limit", [
    (8192, 128 << 20, "stream_resident", 61276160),
    (8192, 64 << 20, "xla", None), (2048, 128 << 20, "xla", None)],
    ids=["the-cell", "a-chip-too-small", "a-whole-row-length"])
def test_plan_at_unequal_head_sizes(force_pallas, monkeypatch, T, capacity,
                                    name, vmem_limit):
    """192 over 128 at the JoyAI cell's shape plans the resident pair
    with the blocks the equal-sized cells have and a budget from both
    sizes (K rows padded to 256 lanes, V rows 128); where the budget does
    not fit, or a whole-row regime owns the length, the call is XLA's."""
    monkeypatch.setattr(fa, "_vmem_capacity", lambda: capacity)
    plan = fa._plan("folded", 2, T, T, 32, 192, 2, True, 128)
    assert (plan.name, plan.vmem_limit) == (name, vmem_limit)
    if name == "stream_resident":
        assert (plan.fwd, plan.bwd) == ((1024, 1024, 1), (512, 512, 1))
        need = fa._resident_vmem_bytes(T, 192, 2, 512, 512, 128)
        assert need < fa._resident_vmem_bytes(T, 256, 2, 512, 512)
        assert need > fa._resident_vmem_bytes(T, 128, 2, 512, 512)
    # equal sizes named twice plan as they do named once
    assert fa._plan("folded", 2, T, T, 32, 128, 2, True, 128) \
        == fa._plan("folded", 2, T, T, 32, 128, 2, True)


@pytest.mark.parametrize("shape,d_v,want", [
    (("stacked", 32, 1024, 1024, 16, 64), (),
     ("packed_mid", (512, 512, 1), (512, 512, 1), None)),
    (("folded", 4, 8192, 8192, 32, 64), (64,),
     ("stream_resident", (1024, 1024, 1), (512, 512, 1), 44564480)),
    (("folded", 4, 8192, 8192, 16, 256), (256,),
     ("stream_resident", (1024, 1024, 1), (512, 512, 1), 77332480)),
    (("folded", 2, 8192, 8192, 32, 192), (128,),
     ("stream_resident", (1024, 1024, 1), (512, 512, 1), 61276160))],
    ids=["gpt2-medium", "lfm2-24b-a2b", "qwen3-next-80b-a3b",
         "joyai-llm-flash"])
def test_the_cells_plans(force_pallas, monkeypatch, shape, d_v, want):
    """The record ``_plan`` hands each benchmark cell's attention call on
    a v5e (128 MiB of VMEM a core), as the public entries ask for it —
    ``flash_attention`` names the v head size, the stacked entry does
    not.  A change to ``_plan`` or to the budget that moves one of these
    moves a measured cell."""
    monkeypatch.setattr(fa, "_vmem_capacity", lambda: 128 << 20)
    plan = fa._plan(*shape, 2, True, *d_v)
    assert plan == fa._Plan(want[0], jax.default_backend() != "tpu",
                            *want[1:])


def test_no_module_imports_a_kernel_modules_private_names():
    """The helpers every kernel family shares (``dot``, ``divisor``,
    ``traced_once``, ``axes_entry``, ...) live in ``ops.pallas`` itself:
    no module of the package imports a name that starts with ``_`` from
    a module under ``ops/pallas/``.  Importing a kernel module under a
    private alias (``from .pallas import rope as _kernels``) is fine."""
    root = pathlib.Path(fa.__file__).parents[3]
    found = []
    for path in sorted((root / "paddle_tpu").rglob("*.py")):
        package = path.parent.relative_to(root).parts
        for node in ast.walk(ast.parse(path.read_text(), str(path))):
            if not isinstance(node, ast.ImportFrom):
                continue
            module = (node.module or "").split(".") if node.module else []
            if node.level:
                module = [*package[:len(package) - node.level + 1], *module]
            if module[:3] != ["paddle_tpu", "ops", "pallas"]:
                continue
            found += [f"{path.relative_to(root)}:{node.lineno} "
                      f"{'.'.join(module)}.{a.name}"
                      for a in node.names if a.name.startswith("_")]
    assert not found, found
