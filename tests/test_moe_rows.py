"""The expert layer's shuffle as the Pallas row-gather pair
(``ops/moe_rows.py``, ``ops/pallas/moe_rows.py``), interpreted on the CPU,
against the XLA ``_take_rows`` / ``_spread_rows`` of
``meta_parallel/moe.py``: values and reverse passes at bfloat16 and
float32 over seeded plans — a held share of the router's width (8 of 64,
16 of 512), a buffer small enough to overflow, tiles wholly past the live
rows, tokens with no held expert and tokens with all k — and the layer
through either path.

Tolerances, where a result is not compared to the bit:

- the gathers sum a token's rows in float32 in the order of their
  experts (the matmul over the tile's staged runs), the XLA path in the
  order of j: a few units in the last place of the dtype;
- the combine's weight is rounded to the dtype, as the XLA path rounds
  it, and the product is exact in float32, where the XLA path rounds
  ``out * w`` to the dtype before summing: to within that rounding;
- the weight's gradient is a float32 dot product over D on the MXU, not
  in XLA's order: ``rtol`` 1e-5 against float32 math.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.distributed.fleet.meta_parallel import moe
from paddle_tpu.ops import moe_rows, pallas
from paddle_tpu.ops.pallas import moe_rows as kernels


@pytest.fixture(autouse=True)
def interpreted(monkeypatch):
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")


def f32(a):
    return np.asarray(jnp.asarray(a, jnp.float32))


def routed(seed, N, k, E, held, cap, D, dtype, skew=None):
    """A seeded routing of N tokens (k distinct experts each of E, ``skew``
    an expert that every token takes first) and its plan."""
    r = np.random.default_rng(seed)
    idx = np.stack([r.choice(E, k, replace=False) for _ in range(N)])
    if skew is not None:
        idx[:, 0] = np.where(idx[:, 0] == skew, idx[:, 0], skew)
        idx[:, 1:] = np.where(idx[:, 1:] == skew, (skew + 1) % E,
                              idx[:, 1:])
    idx = jnp.asarray(idx, jnp.int32)
    w = jnp.asarray(r.random((N, k)), jnp.float32)
    p = moe._routing_plan(idx, 0, held, 1, cap)
    plan = kernels.plan(N, k, D, cap, held, dtype, interpret=True)
    assert plan is not None
    case = {
        "idx": idx, "w": w, "p": p, "N": N, "k": k, "held": held,
        "cap": cap,
        "z": jnp.asarray(r.standard_normal((N, D)), dtype),
        "out": jnp.asarray(r.standard_normal((cap, D)), dtype),
        "dy": jnp.asarray(r.standard_normal((N, D)), dtype),
        "dxs": jnp.asarray(r.standard_normal((cap, D)), dtype),
        "src": p["slot"] // k,
        "tiles": moe_rows.runs(p["pos"], p["valid"], p["group"], held,
                               plan),
        "to_rows": (p["slot"] // k, p["row_valid"], p["pos"], p["valid"]),
        "plan": plan}
    return case


def kernel_dispatch(c):
    return lambda z: moe_rows.dispatch(z, c["tiles"], c["p"]["kept"],
                                       R=c["cap"], cap=c["cap"],
                                       groups=c["held"], plan=c["plan"])


def kernel_combine(c):
    return lambda out, w: moe_rows.combine(
        out, w, c["tiles"], c["p"]["kept"], cap=c["cap"], groups=c["held"],
        plan=c["plan"])


def float32_combine(c):
    """sum_j valid w out[pos] in float32, the weight rounded to out's
    dtype, rounded once."""
    p = c["p"]

    def f(out, w):
        acc = jnp.zeros((c["N"], out.shape[1]), jnp.float32)
        # rounded going forward, the gradient in float32 as the kernel's
        w = w + jax.lax.stop_gradient(
            w.astype(out.dtype).astype(jnp.float32) - w)
        for j in range(c["k"]):
            acc = acc + jnp.where(p["valid"][:, j, None], w[:, j, None]
                                  * out[p["pos"][:, j]].astype(jnp.float32),
                                  0)
        return acc.astype(out.dtype)
    return f


def xla_combine(c):
    p = c["p"]

    def f(out, w):
        w_row = moe._take_rows(
            w.reshape(-1, 1), p["slot"], p["row_valid"],
            p["pos"].reshape(-1, 1), p["valid"].reshape(-1, 1))
        return moe._spread_rows(out * w_row.astype(out.dtype), *c["to_rows"])
    return f


# (N, k, E, held, cap, skew): 8 of 64 with tiles past the live rows and
# tokens that hold none; 16 of 512 (k = 10); every expert held (every
# token all k); a skewed router into a buffer that overflows; and over
# eight tiles of tokens, whose runs share 8-row HBM tiles with the next
# tile's: Mellum2's buffer of four times the expected live rows, and a
# skewed router overflowing it
PLANS = {
    "8-of-64": (256, 8, 64, 8, 1024, None),
    "16-of-512": (512, 10, 512, 16, 512, None),
    "all-held": (128, 4, 8, 8, 512, None),
    "overflow": (128, 4, 8, 8, 128, 3),
    "8-tiles-factor-4": (2048, 8, 64, 8, 8192, None),
    "8-tiles-overflow": (2048, 4, 8, 8, 4096, 3),
}
MANY_TILES = (PLANS["8-tiles-factor-4"], PLANS["8-tiles-overflow"])
DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES.keys())
@pytest.mark.parametrize("shape", PLANS.values(), ids=PLANS.keys())
def test_the_pair_against_the_xla_gathers(shape, dtype):
    N, k, E, held, cap, skew = shape
    c = routed(sum(shape[:5]), N, k, E, held, cap, 128, dtype, skew)
    p = c["p"]
    # the plan counts what the integer routing gives
    idx = np.asarray(c["idx"])
    counts = [(idx == e).sum() for e in range(held)]
    assert np.asarray(p["counts"]).ravel().tolist() == counts
    assert int(p["overflow"]) == max(sum(counts) - cap, 0)
    assert int(p["kept"][0]) == min(sum(counts), cap)
    valid = np.asarray(p["valid"]).sum(1)
    if shape in (PLANS["8-of-64"], PLANS["16-of-512"],
                 PLANS["8-tiles-factor-4"]):
        assert valid.min() == 0                  # tokens that hold none
        assert cap - int(p["kept"][0]) >= 64     # whole tiles of nought
    if shape == PLANS["all-held"]:
        assert valid.min() == k
    if shape in MANY_TILES:
        assert N // c["plan"].tokens == 8
    if shape == PLANS["8-tiles-factor-4"]:
        assert int(p["kept"][0]) % 8             # a part tile at the end

    # the dispatch, copies: to the bit; its reverse pass a float32 sum
    ulp = float(jnp.finfo(dtype).eps)
    xs, back = jax.vjp(kernel_dispatch(c), c["z"])
    want, want_back = jax.vjp(lambda z: moe._take_rows(z, *c["to_rows"]),
                              c["z"])
    np.testing.assert_array_equal(f32(xs), f32(want))
    want_dz = f32(want_back(c["dxs"])[0])
    np.testing.assert_allclose(f32(back(c["dxs"])[0]), want_dz,
                               rtol=4 * ulp,
                               atol=4 * ulp * np.max(np.abs(want_dz)))

    # the combine: against float32 math
    y, vjp = jax.vjp(kernel_combine(c), c["out"], c["w"])
    y32, vjp32 = jax.vjp(float32_combine(c), c["out"], c["w"])
    np.testing.assert_allclose(f32(y), f32(y32), rtol=4 * ulp,
                               atol=4 * ulp * np.max(np.abs(f32(y32))))
    # ... and against the XLA path, which rounds w out before the sum
    want_y = f32(xla_combine(c)(c["out"], c["w"]))
    np.testing.assert_allclose(f32(y), want_y, rtol=4 * ulp,
                               atol=4 * ulp * np.max(np.abs(want_y)))
    dout, dw = vjp(c["dy"])
    dout32, dw32 = vjp32(c["dy"])
    np.testing.assert_allclose(f32(dout), f32(dout32), rtol=ulp, atol=0)
    np.testing.assert_allclose(f32(dw), f32(dw32), rtol=1e-5,
                               atol=1e-5 * np.max(np.abs(f32(dw32))))
    assert not np.any(f32(dw)[~np.asarray(p["valid"])])


@pytest.mark.parametrize("shape", PLANS.values(), ids=PLANS.keys())
def test_the_windows_hold_every_row_once(shape):
    """Each tile's windows are whole 8-row tiles, in order and disjoint,
    and together hold every row the tile's assignments have; each
    assignment's staged row is its buffer row's place among them."""
    N, k, E, held, cap, skew = shape
    c = routed(sum(shape[:5]), N, k, E, held, cap, 128, jnp.bfloat16, skew)
    starts, rows, s = (np.asarray(a) for a in c["tiles"])
    tn, T = c["plan"].tokens, N // c["plan"].tokens
    pos, valid = np.asarray(c["p"]["pos"]), np.asarray(c["p"]["valid"])
    assert not np.any(starts % 8) and not np.any(rows % 8)
    for t in range(T):
        w = [(a, n) for a, n in zip(starts[t * held:(t + 1) * held],
                                    rows[t * held:(t + 1) * held]) if n]
        staged = np.concatenate([np.arange(a, a + n) for a, n in w]
                                or [np.zeros(0, int)])
        assert np.all(np.diff(staged) > 0)                 # disjoint
        mine = pos[t * tn:(t + 1) * tn][valid[t * tn:(t + 1) * tn]]
        got = s[t * tn:(t + 1) * tn][valid[t * tn:(t + 1) * tn]]
        np.testing.assert_array_equal(staged[got], mine)
    assert np.all(s[~valid] == -1)


@pytest.mark.parametrize("D,dtype,cap,tiles", [
    (100, jnp.float32, 4096, None),          # a row is not whole lanes
    (128, jnp.float16, 4096, None),          # neither bf16 nor f32
    (128, jnp.bfloat16, 4092, None),         # ranks of part of a tile
    (2304, jnp.bfloat16, 4096, (256, 2176)),
    (7168, jnp.bfloat16, 4096, (128, 1152)),
])
def test_the_plan_refuses_what_does_not_tile(D, dtype, cap, tiles):
    """Mellum2's and JoyAI's rows: the tile of tokens and the rows it
    stages at most (8 of 8 groups a token, and up to 14 rows of
    neighbours a window), in whole blocks of 128."""
    plan = kernels.plan(2048, 8, D, cap, 8, dtype, interpret=False)
    assert (plan and plan[:2]) == tiles


@pytest.mark.parametrize("dtype", DTYPES.values(), ids=DTYPES.keys())
def test_the_layer_takes_the_pair_and_equals_the_xla_layer(dtype,
                                                           monkeypatch):
    """``routed_experts`` with the kernels interpreted against the same
    layer with the XLA gathers: the same counts and overflow, the output
    and every gradient within the combine's rounding."""
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    D, F, E, held = 128, 32, 16, 8
    p = {"x": jax.random.normal(ks[0], (2, 64, D), dtype),
         "router_w": jax.random.normal(ks[1], (D, E)) * 0.3,
         "w1": jax.random.normal(ks[2], (held, D, F), dtype) * 0.1,
         "w3": jax.random.normal(ks[3], (held, D, F), dtype) * 0.1,
         "w2": jax.random.normal(ks[4], (held, F, D), dtype) * 0.1}
    bias = jax.random.normal(ks[5], (E,)) * 0.01

    def layer(p):
        return moe.routed_experts(p["x"], p["router_w"], bias, p["w1"],
                                  p["w3"], p["w2"], top_k=4, rows=256)

    def loss(p):
        y, _, _ = layer(p)
        return jnp.sum(jnp.sin(y.astype(jnp.float32)))

    before = pallas.selections().get("moe_rows.interpret", 0)
    got = layer(p)
    got_grad = jax.grad(loss)(p)
    assert pallas.selections()["moe_rows.interpret"] > before
    monkeypatch.delenv("PADDLE_PALLAS_FORCE")
    before = pallas.selections().get("moe_rows.xla", 0)
    want = layer(p)
    want_grad = jax.grad(loss)(p)
    assert pallas.selections()["moe_rows.xla"] > before
    assert got[1].tolist() == want[1].tolist()
    assert int(got[2]) == int(want[2])
    tol = 2e-2 if dtype == jnp.bfloat16 else 1e-5
    np.testing.assert_allclose(f32(got[0]), f32(want[0]), rtol=tol,
                               atol=tol)
    for name in got_grad:
        scale = np.max(np.abs(f32(want_grad[name])))
        np.testing.assert_allclose(f32(got_grad[name]),
                                   f32(want_grad[name]), rtol=tol,
                                   atol=tol * scale, err_msg=name)


def test_over_ep_the_pair_equals_the_xla_layer(monkeypatch):
    """Over a CPU mesh of ``ep`` = 4 (the rows travel by ``all_to_all``
    and ``_exchange`` keeps XLA's gathers): each rank's dispatch and
    combine through the pair, interpreted, against the XLA layer."""
    from paddle_tpu.distributed.topology import build_mesh
    ks = jax.random.split(jax.random.PRNGKey(3), 6)
    D, F, E, held = 128, 32, 16, 8
    p = {"x": jax.random.normal(ks[0], (8, 32, D)),
         "router_w": jax.random.normal(ks[1], (D, E)) * 0.3,
         "w1": jax.random.normal(ks[2], (held, D, F)) * 0.1,
         "w3": jax.random.normal(ks[3], (held, D, F)) * 0.1,
         "w2": jax.random.normal(ks[4], (held, F, D)) * 0.1}
    bias = jax.random.normal(ks[5], (E,)) * 0.01
    mesh = build_mesh({"ep": 4}, devices=jax.devices()[:4])

    def layer(p):
        return moe.routed_experts(p["x"], p["router_w"], bias, p["w1"],
                                  p["w3"], p["w2"], top_k=4, mesh=mesh,
                                  token_axes=("ep",), ep_axis="ep")

    def loss(p):
        return jnp.sum(jnp.sin(layer(p)[0]))

    got, got_grad = jax.jit(layer)(p), jax.jit(jax.grad(loss))(p)
    monkeypatch.delenv("PADDLE_PALLAS_FORCE")
    want, want_grad = jax.jit(layer)(p), jax.jit(jax.grad(loss))(p)
    assert got[1].tolist() == want[1].tolist()
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5, atol=1e-6)
    for name in got_grad:
        np.testing.assert_allclose(got_grad[name], want_grad[name],
                                   rtol=1e-4, atol=1e-5, err_msg=name)
