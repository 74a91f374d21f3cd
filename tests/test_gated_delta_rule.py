"""The chunked gated delta rule (``ops/gated_delta_rule.py``) against the
recurrence token by token, in float32 on the CPU: the output and all five
gradients (q, k, v, g, beta), at a length the chunk does not divide, with
a mild gate and with a gate down to -20 a token — where ``exp(G_i)
exp(-G_j)`` would overflow and the chunked form, which only ever takes
``exp(G_i - G_j)`` with i >= j, must not.

Every case runs twice: at a small shape that no kernel tiles (the loop
over chunks is the ``lax.scan``), and at head size 128 with
``PADDLE_PALLAS_FORCE=1``, where the loop is the Pallas kernels of
``ops/pallas/gated_delta_rule.py`` in interpret mode — the forward, the
state pass and the hand-written reverse pass — and the prep in front of
it the kernel pair ``delta_rule_prep`` / ``delta_rule_prep_bwd``, held
here to ``_prep`` (XLA ops, ``solve_triangular``) and its ``jax.vjp``.
"""
import functools

from typing import NamedTuple

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.ops import gated_delta_rule as rule_module
from paddle_tpu.ops import pallas
from paddle_tpu.ops.gated_delta_rule import (gated_delta_rule,
                                             gated_delta_rule_reference)
from paddle_tpu.ops.pallas import gated_delta_rule as kernels

NAMES = ("q", "k", "v", "g", "beta")
B = 2


class Shape(NamedTuple):
    T: int
    H_k: int
    H: int
    dk: int
    dv: int
    chunk: int
    kernels: bool


# 37 = 4 chunks of 8 + 5; 56 = 3 chunks of 16 + 8: both need the padded
# tail.  The second tiles: whole lanes a head, whole bf16 sublane tiles a
# chunk, 2 key heads under 4 value heads
SHAPES = {"xla": Shape(37, 3, 3, 8, 6, 8, False),
          "kernels": Shape(56, 2, 4, 128, 128, 16, True)}
PATHS = list(SHAPES)


@pytest.fixture
def path(request, monkeypatch):
    """The shape of a path, with the kernels forced on for the second."""
    shape = SHAPES[request.param]
    if shape.kernels:
        monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    return shape


def _inputs(seed, g_min, shape, T=None, heads_k=None):
    T = T or shape.T
    heads_k = heads_k or shape.H_k
    ks = jax.random.split(jax.random.PRNGKey(seed), 5)
    unit = lambda x: x / jnp.linalg.norm(x, axis=-1, keepdims=True)  # noqa
    q = unit(jax.random.normal(ks[0], (B, T, heads_k, shape.dk))) \
        / np.sqrt(shape.dk)
    k = unit(jax.random.normal(ks[1], (B, T, heads_k, shape.dk)))
    v = jax.random.normal(ks[2], (B, T, shape.H, shape.dv))
    g = g_min * jax.random.uniform(ks[3], (B, T, shape.H))
    beta = jax.nn.sigmoid(jax.random.normal(ks[4], (B, T, shape.H)))
    return q, k, v, g, beta


def _rep(x, shape):
    return jnp.repeat(x, shape.H // x.shape[2], axis=2)


@pytest.fixture(scope="module",
                params=[(g, p) for p in PATHS for g in (-0.5, -20.0)],
                ids=lambda gp: f"{gp[1]}-" + ("mild-gate" if gp[0] > -1
                                             else "gate-to-minus-20"))
def both(request):
    """(outputs, gradients) of the chunked rule and of the recurrence,
    each under the same random cotangent."""
    g_min, name = request.param
    shape = SHAPES[name]
    x = _inputs(0, g_min, shape)
    w = jax.random.normal(jax.random.PRNGKey(9),
                          (B, shape.T, shape.H, shape.dv))

    def run(fn):
        def loss(*a):
            o = fn(*a)
            return jnp.sum(o * w), o
        (_, o), grads = jax.jit(jax.value_and_grad(
            loss, argnums=(0, 1, 2, 3, 4), has_aux=True))(*x)
        return o, dict(zip(NAMES, grads))

    with pytest.MonkeyPatch.context() as mp:
        if shape.kernels:
            mp.setenv("PADDLE_PALLAS_FORCE", "1")
        before = pallas.selections()
        chunked = run(lambda *a: gated_delta_rule(*a, chunk=shape.chunk))
        impl = "interpret" if shape.kernels else "xla"
        assert pallas.selections().get(f"gated_delta_rule.{impl}", 0) \
            == before.get(f"gated_delta_rule.{impl}", 0) + 1
    return (chunked,
            run(lambda q, k, *a: gated_delta_rule_reference(
                _rep(q, shape), _rep(k, shape), *a)),
            shape)


def test_the_output_equals_the_recurrence(both):
    (o, _), (want, _), shape = both
    assert o.shape == (B, shape.T, shape.H, shape.dv)
    assert bool(jnp.all(jnp.isfinite(o)))
    # float32 sums in another order: a chunk's writes at once
    np.testing.assert_allclose(o, want, rtol=1e-4, atol=2e-6)


@pytest.mark.parametrize("name", NAMES)
def test_every_gradient_equals_the_recurrence_s(both, name):
    (_, got), (_, want), _ = both
    assert bool(jnp.all(jnp.isfinite(got[name])))
    scale = float(jnp.max(jnp.abs(want[name])))
    np.testing.assert_allclose(got[name], want[name], rtol=1e-3,
                               atol=1e-5 * scale)


@pytest.mark.parametrize("name", PATHS)
def test_the_strong_gate_reaches_the_overflow_arm(name):
    """In the second case a chunk's cumulative log-decay passes float32's
    exp range: ``exp(-G_j)`` alone would be inf."""
    shape = SHAPES[name]
    T = shape.T - shape.T % shape.chunk
    g = np.asarray(_inputs(0, -20.0, shape)[3])[:, :T]
    G = np.cumsum(g.reshape(B, -1, shape.chunk, shape.H), axis=2)
    assert g.min() < -19 and G.min() < -89    # exp(89) > float32's largest
    with np.errstate(over="ignore"):
        assert not np.isfinite(np.exp(np.float32(-G.min())))


@pytest.mark.parametrize("path,T_,chunk", [
    ("xla", 16, 8), ("xla", 5, 8), ("xla", 33, 16),
    ("kernels", 64, 16), ("kernels", 5, 16), ("kernels", 40, 16)],
    ids=lambda x: str(x), indirect=["path"])
def test_any_length(path, T_, chunk):
    x = _inputs(1, -2.0, path, T=T_)
    want = gated_delta_rule_reference(_rep(x[0], path), _rep(x[1], path),
                                      *x[2:])
    np.testing.assert_allclose(gated_delta_rule(*x, chunk=chunk), want,
                               rtol=1e-4, atol=2e-6)


@pytest.mark.parametrize("path", PATHS, indirect=True)
def test_key_heads_serve_groups_of_value_heads(path):
    """All value heads over 1 key head: as if q and k were repeated."""
    q, k, v, g, beta = _inputs(2, -1.0, path, heads_k=1)
    f = lambda q, k: jnp.sum(jnp.sin(   # noqa: E731
        gated_delta_rule(q, k, v, g, beta, chunk=path.chunk)))
    f_rep = lambda q, k: jnp.sum(jnp.sin(gated_delta_rule_reference(   # noqa
        _rep(q, path), _rep(k, path), v, g, beta)))
    # a sum over every output that cancels to a tenth: float32's noise
    # over the kernels' 57 344 terms is 1e-5 of it
    np.testing.assert_allclose(f(q, k), f_rep(q, k),
                               rtol=1e-4 if path.kernels else 1e-5)
    for got, want in zip(jax.grad(f, (0, 1))(q, k),
                         jax.grad(f_rep, (0, 1))(q, k)):
        np.testing.assert_allclose(got, want, rtol=1e-3, atol=1e-6)


@pytest.fixture(scope="module",
                params=[(p, hk) for p in PATHS for hk in ("fewer", "equal")],
                ids=lambda x: f"{x[0]}-{x[1]}-key-heads")
def folded(request):
    """(output and five gradients through the token-major entry, the same
    through the (B, T, H, d) entry, the path's shape): key heads 2 -> 4
    value heads (the xla shape: 1 -> 3) and as many key heads as value
    heads; T needs the padded tail."""
    name, heads = request.param
    shape = SHAPES[name]
    H_k = shape.H if heads == "equal" else (shape.H_k if shape.kernels else 1)
    x = _inputs(12, -2.0, shape, heads_k=H_k)
    w = jax.random.normal(jax.random.PRNGKey(13),
                          (B, shape.T, shape.H, shape.dv))

    def fold(a):
        return a.reshape(B, shape.T, -1)

    def by_heads(q, k, v, g, beta):
        o = gated_delta_rule(q, k, v, g, beta, chunk=shape.chunk)
        return jnp.sum(o * w), o

    def token_major(q, k, v, g, beta):
        o = gated_delta_rule(q, k, v, g, beta, chunk=shape.chunk,
                             key_heads=H_k)
        assert o.shape == (B, shape.T, shape.H * shape.dv)
        return jnp.sum(o * fold(w)), o

    def run(fn, x):
        (_, o), grads = jax.jit(jax.value_and_grad(
            fn, argnums=(0, 1, 2, 3, 4), has_aux=True))(*x)
        return {"o": o, **dict(zip(NAMES, grads))}

    with pytest.MonkeyPatch.context() as mp:
        if shape.kernels:
            mp.setenv("PADDLE_PALLAS_FORCE", "1")
        want = run(by_heads, x)
        got = run(token_major, (*map(fold, x[:3]), *x[3:]))
    return got, want, shape


@pytest.mark.parametrize("name", ("o", *NAMES))
def test_the_token_major_entry_is_the_entry_by_heads(folded, name):
    """q, k (B, T, H_k d_k) with ``key_heads`` and v (B, T, H d_v) give
    what (B, T, H, d) gives, the output and the cotangents of q, k and v
    in the form they came in: off the TPU bit for bit (the XLA math
    reshapes for itself), and the interpreted kernels — which read and
    write the token-major form either way — within the recurrence's
    tolerances."""
    got, want, shape = folded
    got, want = got[name], want[name]
    if name in ("o", "q", "k", "v"):
        assert got.ndim == 3 and want.ndim == 4
        got = got.reshape(want.shape)
    if shape.kernels:
        np.testing.assert_allclose(
            got, want, rtol=1e-4,
            atol=2e-6 * max(1.0, float(jnp.max(jnp.abs(want)))))
    else:
        np.testing.assert_array_equal(got, want)


def test_the_token_major_entry_needs_the_key_heads():
    """Three axes no longer say where a key head ends."""
    q, k, v, g, beta = _inputs(14, -1.0, SHAPES["xla"], T=8)
    with pytest.raises(ValueError, match="key_heads"):
        gated_delta_rule(*(a.reshape(B, 8, -1) for a in (q, k, v)), g, beta,
                         chunk=8)


@pytest.mark.parametrize("path", PATHS, indirect=True)
def test_bfloat16_operands_keep_the_state_in_float32(path):
    """A bf16 step hands bf16 q, k, v; g, the decays and the state stay
    float32: the result is bf16 and near the float32 rule (bf16 has 8
    bits: a percent of the largest output)."""
    q, k, v, g, beta = _inputs(3, -2.0, path)
    o = gated_delta_rule(*(a.astype(jnp.bfloat16) for a in (q, k, v)),
                         g, beta, chunk=path.chunk)
    assert o.dtype == jnp.bfloat16
    want = gated_delta_rule_reference(_rep(q, path), _rep(k, path), v, g,
                                      beta)
    assert float(jnp.max(jnp.abs(o.astype(jnp.float32) - want))) \
        < 0.03 * float(jnp.max(jnp.abs(want)))


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-5),
                                       (jnp.bfloat16, 2e-2)],
                         ids=["float32", "bfloat16"])
def test_the_reverse_pass_is_the_vjp_of_the_scan_form(dtype, tol):
    """The kernels' own backward (state pass, reverse pass) on the loop's
    six operands against ``jax.vjp`` of the ``lax.scan`` form, and the
    forward kernel against the scan's output.  In bfloat16 the kernels
    round a cotangent where it enters a product, as a TPU's default
    precision does and the CPU's float32 product does not."""
    shape = SHAPES["kernels"]
    T = 64
    q, k, v, g, beta = _inputs(4, -2.0, shape, T=T)
    q, k, v = (a.astype(dtype) for a in (q, k, v))
    operands = jax.vmap(
        lambda *x: rule_module._prep(*x, chunk=shape.chunk))(q, k, v, g,
                                                             beta)
    do = jax.random.normal(jax.random.PRNGKey(5), v.shape).astype(dtype)
    want_o, vjp = jax.vjp(jax.vmap(
        lambda *x: rule_module._chunk_scan_xla(*x, dtype)), *operands)
    plan = kernels.plan(T // shape.chunk, shape.H, shape.chunk, shape.dk,
                        shape.dv, interpret=True)
    got_o = kernels.chunk_scan(*operands, out_dtype=dtype, plan=plan)
    assert got_o.shape == (B, T, shape.H * shape.dv)    # token-major
    f32 = lambda a: np.asarray(a, np.float32)   # noqa: E731
    np.testing.assert_allclose(f32(got_o).reshape(want_o.shape),
                               f32(want_o), rtol=tol,
                               atol=tol * float(np.abs(f32(want_o)).max()))
    got = kernels.chunk_scan_vjp(*operands, do.reshape(got_o.shape),
                                 plan=plan)
    for name, a, b, x in zip(("w_k", "w_v", "attn", "q_dec", "k_dec",
                              "last"), got, vjp(do), operands):
        assert a.shape == x.shape and a.dtype == x.dtype, name
        np.testing.assert_allclose(
            f32(a), f32(b), rtol=tol,
            atol=tol * float(np.abs(f32(b)).max()), err_msg=name)


def _padded_row(seed, g_min, T, dtype, chunk=None, heads_k=None):
    """Row 0 of ``_inputs`` at the kernels' shape, q, k, v in ``dtype``,
    padded to whole chunks as ``gated_delta_rule`` pads."""
    shape = SHAPES["kernels"]
    chunk = chunk or shape.chunk
    q, k, v, g, beta = (a[0] for a in _inputs(seed, g_min, shape, T=T,
                                              heads_k=heads_k))
    pad = lambda a: jnp.pad(   # noqa: E731
        a, ((0, -T % chunk),) + ((0, 0),) * (a.ndim - 1))
    return tuple(map(pad, (q.astype(dtype), k.astype(dtype),
                           v.astype(dtype), g, beta)))


def _folded(*rows):
    """Rows (T, heads, d) token-major, (T, heads d), as the kernels'
    wrappers take q, k and v."""
    return tuple(a.reshape(a.shape[0], -1) for a in rows)


def _prep_plan(x, chunk):
    q, _, v = x[:3]
    return kernels.plan(v.shape[0] // chunk, v.shape[1], chunk, q.shape[-1],
                        v.shape[-1], interpret=True, H_k=q.shape[1])


OPERANDS = ("w_k", "w_v", "attn", "q_dec", "k_dec", "last")
PREP_CASES = pytest.mark.parametrize(
    "T,dtype,tol", [(56, jnp.float32, 1e-5), (64, jnp.float32, 1e-5),
                    (56, jnp.bfloat16, 2e-2), (64, jnp.bfloat16, 2e-2)],
    ids=["padded-float32", "whole-float32", "padded-bfloat16",
         "whole-bfloat16"])


def _close(got, want, tol, name):
    f32 = lambda a: np.asarray(a, np.float32)   # noqa: E731
    assert got.shape == want.shape and got.dtype == want.dtype, name
    np.testing.assert_allclose(f32(got), f32(want), rtol=tol,
                               atol=tol * float(np.abs(f32(want)).max()),
                               err_msg=name)


@PREP_CASES
def test_the_prep_kernel_makes_the_operands_of_the_xla_prep(T, dtype, tol):
    """``delta_rule_prep`` (the gates in front of it XLA's) against
    ``_prep``: the same six operands, the same cast points."""
    chunk = SHAPES["kernels"].chunk
    x = _padded_row(8, -2.0, T, dtype)
    want = rule_module._prep(*x, chunk)
    got, inv = rule_module._operands(
        *_folded(*x[:3]), rule_module._gates(*x[3:], chunk),
        _prep_plan(x, chunk))
    assert inv is None
    for name, a, b in zip(OPERANDS, got, want):
        _close(a[0], b, tol, name)


@PREP_CASES
def test_the_prep_s_reverse_kernel_is_the_vjp_of_the_xla_prep(T, dtype,
                                                                tol):
    """``delta_rule_prep_bwd`` against ``jax.vjp`` of ``_prep`` on random
    cotangents of all six operands; dq and dk come summed over the value
    heads a key head serves.  In bfloat16 the kernel rounds a cotangent
    where it enters a product and sums a key head's shares in float32,
    autodiff in bfloat16."""
    chunk = SHAPES["kernels"].chunk
    x = _padded_row(9, -2.0, T, dtype)
    want, vjp = jax.vjp(functools.partial(rule_module._prep, chunk=chunk),
                        *x)
    cotangents = tuple(
        jax.random.normal(key, w.shape).astype(w.dtype)
        for key, w in zip(jax.random.split(jax.random.PRNGKey(10), 6),
                          want))
    plan = _prep_plan(x, chunk)
    gates, gates_vjp = jax.vjp(
        functools.partial(rule_module._gates, chunk=chunk), *x[3:])
    flat = _folded(*x[:3])
    _, inv = rule_module._operands(*flat, gates, plan, with_inverse=True)
    *dqkv, dG, d_beta = kernels.prep_vjp(
        *(a[None] for a in (*flat, *gates[:2])), inv,
        *(c[None] for c in cotangents[:5]), plan=plan)
    for d, a in zip(dqkv, flat):                        # token-major
        assert d.shape == (1, *a.shape) and d.dtype == a.dtype
    got = (*(d.reshape(a.shape) for d, a in zip(dqkv, x)),
           *gates_vjp((dG[0], d_beta[0], cotangents[5])))
    for name, a, b in zip(NAMES, got, vjp(cotangents)):
        _close(a, b, tol, name)


def _corner(chunk):
    """beta -> 1 over near-parallel keys with hardly any decay: ``I + A``
    is near the all-ones triangle, whose inverse cancels row against
    row."""
    shape = SHAPES["kernels"]
    T = 2 * chunk
    keys = jax.random.split(jax.random.PRNGKey(11), 4)
    k = jax.random.normal(keys[0], (1, shape.H_k, shape.dk)) \
        + 0.05 * jax.random.normal(keys[1], (T, shape.H_k, shape.dk))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    v = jax.random.normal(keys[2], (T, shape.H, shape.dv))
    g = -1e-3 * jax.random.uniform(keys[3], (T, shape.H))
    return k, k, v, g, jnp.full((T, shape.H), 0.999)


@pytest.mark.parametrize("chunk", [16, 64], ids=["chunk-16", "chunk-64"])
@pytest.mark.parametrize("case", ["ill-conditioned", "strong-gate"])
def test_the_inverse_in_vmem_is_solve_triangular_s(case, chunk):
    """The kernel's float32 inverse of ``I + A`` — forward substitution on
    the diagonal blocks, then blocks doubled by float32 products (chunk
    64: two levels) — against ``solve_triangular`` at float32 tolerance:
    in the ill-conditioned corner, and under the gate down to -20 a
    token, where the exponent's mask must keep every entry finite."""
    x = _corner(chunk) if case == "ill-conditioned" \
        else _padded_row(0, -20.0, 2 * chunk, jnp.float32, chunk)
    q, k, v, g, beta = x
    G, b, _ = gates = rule_module._gates(g, beta, chunk)
    _, inv = rule_module._operands(*_folded(q, k, v), gates,
                                   _prep_plan(x, chunk), with_inverse=True)
    n, H, C = G.shape
    kk = jnp.repeat(jnp.moveaxis(k.reshape(n, C, *k.shape[1:]), 2, 1),
                    H // k.shape[1], axis=1)
    lower = np.tril(np.ones((C, C), bool), -1)
    with np.errstate(over="ignore"):
        decay = np.exp(np.where(lower, np.asarray(G)[..., :, None]
                                - np.asarray(G)[..., None, :], -np.inf))
    A = np.asarray(b)[..., None] * decay \
        * np.asarray(jnp.einsum("nhcd,nhsd->nhcs", kk, kk))
    want = jax.scipy.linalg.solve_triangular(
        jnp.asarray(A) + jnp.eye(C), jnp.broadcast_to(jnp.eye(C), A.shape),
        lower=True, unit_diagonal=True)
    inv = np.asarray(inv[0])
    assert np.isfinite(inv).all()
    if case == "ill-conditioned":
        # rows cancel: the inverse's entries are of order one while
        # A's below the diagonal are all near one
        assert A[lower[None, None] & np.ones_like(A, bool)].min() > 0.9
    else:
        assert float(np.asarray(G).min()) < -89
    np.testing.assert_allclose(inv, want, rtol=1e-5, atol=1e-5)
    assert (inv[..., ~np.tril(np.ones((C, C), bool))] == 0).all()


@pytest.mark.parametrize("dk,dv,chunk", [(64, 128, 16), (128, 96, 16),
                                         (128, 128, 8), (128, 128, 24)],
                         ids=["narrow-keys", "narrow-values",
                              "chunk-under-a-bf16-tile",
                              "chunk-of-one-and-a-half-tiles"])
def test_a_shape_that_does_not_tile_takes_the_xla_loop(monkeypatch, dk, dv,
                                                       chunk):
    """Which prep and loop run is one function of the backend and the
    shapes (``kernels.plan``): with the kernels forced on, a head size
    that does not fill whole lanes or a chunk that is not whole 16-bit
    sublane tiles still takes ``_prep`` and the ``lax.scan`` — no kernel
    of either — and is counted so."""
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    shape = Shape(48 if chunk == 24 else 32, 2, 4, dk, dv, chunk, False)
    assert kernels.plan(shape.T // chunk, shape.H, chunk, dk, dv,
                        interpret=True, H_k=shape.H_k) is None
    x = _inputs(6, -1.0, shape)
    assert "pallas_call" not in str(jax.make_jaxpr(
        functools.partial(gated_delta_rule, chunk=chunk))(*x))
    before = pallas.selections()
    o = gated_delta_rule(*x, chunk=chunk)
    after = pallas.selections()
    assert after.get("gated_delta_rule.xla", 0) \
        == before.get("gated_delta_rule.xla", 0) + 1
    assert after.get("gated_delta_rule.interpret", 0) \
        == before.get("gated_delta_rule.interpret", 0)
    np.testing.assert_allclose(
        o, gated_delta_rule_reference(_rep(x[0], shape), _rep(x[1], shape),
                                      *x[2:]), rtol=1e-4, atol=2e-6)


def test_under_a_mesh_the_kernels_run_per_shard(monkeypatch):
    """GSPMD cannot partition a Mosaic call: under a mesh of more than
    one device the kernel path sits in a ``shard_map`` over the axes
    that shard the batch, and gives what one device gives."""
    from jax.sharding import NamedSharding, PartitionSpec
    from paddle_tpu.distributed.topology import build_mesh
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    shape = SHAPES["kernels"]
    mesh = build_mesh({"dp": 2})
    x = _inputs(7, -1.0, shape, T=32)
    f = lambda *a, **kw: jnp.sum(jnp.sin(   # noqa: E731
        gated_delta_rule(*a, chunk=shape.chunk, **kw)))
    want = jax.grad(f, (0, 1, 2, 3, 4))(*x)
    rows = NamedSharding(mesh, PartitionSpec("dp"))
    got = jax.jit(jax.grad(
        lambda *a: f(*a, mesh=mesh, batch_axes=("dp",)), (0, 1, 2, 3, 4)))(
            *(jax.device_put(a, rows) for a in x))
    for a, b in zip(got, want):
        assert a.sharding.is_equivalent_to(rows, a.ndim)
        np.testing.assert_allclose(a, b, rtol=1e-5, atol=1e-7)
