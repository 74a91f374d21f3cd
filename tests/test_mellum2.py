"""Mellum2 through ``build_spmd_train_step`` against the plain reference
(``benchmark/references/mellum2.py``: attention one head and one block of
queries at a time under an explicit mask, YaRN written out from its
formula, the experts one at a time), and the flash kernels' window.

Float32 on the CPU at small widths with one whole period (three window
layers, one full layer with YaRN RoPE, every FFN the expert layer):
hidden 128, 4 query heads over 2 KV heads of 32, 8 experts of which 4 are
held, top 2, a window of 64 over T = 256.  The seeded weights are the
reference's own, so one tree serves both sides.  Compared element-wise:
each block kind, the logits and counters, the loss and every gradient
leaf (also through the interpreted window kernels), the parameters after
three AdamW steps.  Then the parts by themselves: YaRN's frequencies and
the factor on cos and sin against the formula by hand, the eight shares
of 64 experts against the uncut layer, ``ep`` over a CPU mesh; the window
kernels forward and backward against the masked XLA math, the lower edge
of ``_live_chunks`` against the mask, the plan of a windowed call; the
fused loss head at the cell's hidden size 2304.

Tolerances: float32 sums in another order (a sort in front of the grouped
matmuls, an online softmax over chunks) are good to 1e-4 of a value; a
gradient leaf to 2e-3 of it with a floor of 1e-6 of the loss's scale;
after three AdamW steps every weight has moved by about the rate whatever
its gradient's size, so parameters agree to 2e-5 absolute.
"""
import functools
import math
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.drivers.mellum2_train import model_config as config_of
from benchmark.references import mellum2 as ref
from paddle_tpu.distributed.fleet.meta_parallel.moe import (
    routed_experts, softmax_topk_routing)
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.models import Mellum2Config
from paddle_tpu.models import mellum2 as model
from paddle_tpu.models.gpt_spmd import build_spmd_train_step
from paddle_tpu.models.sparse_blocks import rope_angles
from paddle_tpu.ops.rope import rope_to_heads

import paddle_tpu.ops.pallas  # noqa: F401  (the module, not the function)
fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]

OPT = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
       "weight_decay": 0.01}
PUBLISHED_ROPE = {
    "full_attention": {
        "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
        "original_max_position_embeddings": 8192, "beta_fast": 32,
        "beta_slow": 1, "attention_factor": 1.2772588722239782},
    "sliding_attention": {"rope_type": "default", "rope_theta": 500000}}
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]
TINY = {
    "hidden_size": 128, "vocab_size": 96, "num_hidden_layers": 4,
    "layer_types": PERIOD * 2, "mlp_layer_types": ["sparse"] * 8,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 32,
    "sliding_window": 64, "use_sliding_window": True,
    "max_window_layers": 0, "rope_parameters": PUBLISHED_ROPE,
    "rms_norm_eps": 1e-6, "num_experts": 4, "num_experts_per_tok": 2,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False,
    "deployment": {"router_width": 8, "first_expert": 4},
    "assumed": {"optimizer": OPT}}
TRAFFIC = {"batch": 2, "seq_len": 256, "pool": 3, "check_steps": 3}
MM = functools.partial(jnp.einsum, precision=ref.HIGHEST)
EPS = TINY["rms_norm_eps"]


def one_device():
    return build_mesh({"dp": 1}, devices=jax.devices()[:1])


def build(c=TINY, mesh=None, **kw):
    step, _ = build_spmd_train_step(
        config_of(c), mesh or one_device(), compute_dtype=jnp.float32,
        learning_rate=OPT["learning_rate"],
        weight_decay=OPT["weight_decay"], **kw)
    return step


def fresh_state(params):
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)   # noqa: E731
    return (jax.tree.map(jnp.copy, params),
            {"m": zeros(), "v": zeros(), "step": jnp.zeros((), jnp.int32)})


def leaves(tree):
    return {jax.tree_util.keystr(k): np.asarray(v) for k, v in
            jax.tree_util.tree_flatten_with_path(tree)[0]}


def assert_trees_close(got, want, rtol, atol):
    got, want = leaves(got), leaves(want)
    assert got.keys() == want.keys()
    for name in want:
        np.testing.assert_allclose(got[name], want[name], rtol=rtol,
                                   atol=atol, err_msg=name)


ref_value_and_grad = jax.jit(jax.value_and_grad(
    lambda p, ids, labels: ref.summed_loss(p, ids, labels, TINY)))


@pytest.fixture(scope="module")
def seeded():
    """The reference's seeded weights, the gains moved off their initial
    1 so that a gain that is dropped shows."""
    params = ref.init_params(TINY, 5)
    ks = iter(jax.random.split(jax.random.PRNGKey(6), 16))
    for p in (*params["layers"], params):
        for name in [n for n in p if n.endswith("_norm")]:
            p[name] = p[name] + 0.1 * jax.random.normal(next(ks),
                                                        p[name].shape)
    batches = [(jnp.asarray(i), jnp.asarray(l))
               for i, l in ref.make_batches(TINY, TRAFFIC, 5)]
    return params, batches


def test_the_models_package_exports_the_configuration():
    cfg = config_of(TINY)
    assert isinstance(cfg, Mellum2Config)
    assert [cfg.window_of(l) for l in range(4)] == [64, 64, 64, None]
    assert cfg.held == 4 and cfg.num_experts == 8 and cfg.first_expert == 4
    assert cfg.yarn == (16.0, 8192, 32.0, 1.0)
    parts = cfg.spmd_parts(one_device())
    assert parts.step_name == "mellum2_spmd_train_step"
    assert parts.keep_float32((jax.tree_util.DictKey("router_w"),))
    # the published defaults are the published config's
    d = Mellum2Config()
    assert (d.hidden_size, d.num_attention_heads, d.num_key_value_heads,
            d.head_dim, d.sliding_window, d.moe_intermediate_size) \
        == (2304, 32, 4, 128, 1024, 896)
    assert list(d.layer_types) == PERIOD * 7
    with pytest.raises(NotImplementedError, match="use_sliding_window"):
        config_of(dict(TINY, use_sliding_window=False))


# ---------------------------------------------------------------------------
# each block kind against the reference's
# ---------------------------------------------------------------------------
def _x(seed, T=256):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, T, 128))


def _kinds():
    cfg, mesh = config_of(TINY), one_device()

    def ref_attention(kind, window):
        return lambda p, x: x + ref.attention_op(
            p, ref._norm(x, p["op_norm"], EPS), TINY, MM, kind, window)

    return {
        "window": (0, lambda p, x: model._attention(p, x, cfg, mesh, (), 64),
                   ref_attention("sliding_attention", 64)),
        "full-yarn": (3, lambda p, x: model._attention(p, x, cfg, mesh, (),
                                                       None),
                      ref_attention("full_attention", None)),
        "experts": (1, lambda p, x: model._expert_ffn(p, x, cfg, mesh,
                                                      None)[0],
                    lambda p, x: x + ref.routed_part(
                        p, ref._norm(x, p["ffn_norm"], EPS), TINY, MM)[0])}


@pytest.mark.parametrize("kind", ["window", "full-yarn", "experts"])
def test_a_block_kind_matches_the_reference(seeded, kind):
    layer, got, want = _kinds()[kind]
    p, x = seeded[0]["layers"][layer], _x(1)
    np.testing.assert_allclose(jax.jit(got)(p, x), jax.jit(want)(p, x),
                               rtol=1e-4, atol=1e-5)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))   # noqa: E731
    assert_trees_close(jax.jit(jax.grad(loss(got), (0, 1)))(p, x),
                       jax.jit(jax.grad(loss(want), (0, 1)))(p, x),
                       rtol=2e-3, atol=2e-5)


def test_the_window_and_yarn_change_the_attention(seeded):
    """Neither is a no-op at these sizes: the window layer differs from
    the same weights seen causally, YaRN's layer from plain RoPE."""
    p, x = seeded[0]["layers"][0], _x(2)
    z = ref._norm(x, p["op_norm"], EPS)
    window = ref.attention_op(p, z, TINY, MM, "sliding_attention", 64)
    causal = ref.attention_op(p, z, TINY, MM, "sliding_attention", None)
    yarn = ref.attention_op(p, z, TINY, MM, "full_attention", None)
    # the first 64 queries see the same keys either way
    np.testing.assert_allclose(window[:, :64], causal[:, :64], rtol=1e-5,
                               atol=1e-6)
    assert float(jnp.max(jnp.abs(window[:, 64:] - causal[:, 64:]))) > 1e-3
    assert float(jnp.max(jnp.abs(yarn - causal))) > 1e-3


def test_logits_and_counters_match_the_reference(seeded):
    params, batches = seeded
    ids = batches[0][0]
    parts = config_of(TINY).spmd_parts(one_device())
    x, counters = parts.trunk(params, ids, lambda f: f)
    want = jax.jit(lambda p, i: ref.logits_of(p, i, TINY))(params, ids)
    np.testing.assert_allclose(x @ params["head_w"], want, rtol=2e-4,
                               atol=2e-5)
    # what the device counts is what the reference's router chose
    _, chosen = jax.jit(lambda p, i: ref.hidden_states(p, i, TINY))(
        params, ids)
    assert counters["moe_counts"].shape == (4, 4)
    for l, idx in enumerate(chosen):
        want_counts = [(np.asarray(idx) == 4 + e).sum() for e in range(4)]
        assert counters["moe_counts"][l].tolist() == want_counts
    assert int(counters["moe_overflow"]) == 0


@pytest.mark.parametrize("policy", ["none", "ctx", "full"])
def test_loss_and_every_gradient_leaf_match_the_reference(seeded, policy):
    params, batches = seeded
    ids, labels = batches[0]
    want_loss, want_grads = ref_value_and_grad(params, ids, labels)
    n = ids.size
    loss, _p, opt_state, counters = build(remat_policy=policy)(
        *fresh_state(params), ids, labels)
    np.testing.assert_allclose(loss, want_loss / n, rtol=1e-5)
    assert int(counters["moe_overflow"]) == 0
    # the first gradient as the optimizer got it: m1 = (1 - beta1) g
    grads = jax.tree.map(lambda m: m / (1 - OPT["beta1"]), opt_state["m"])
    assert_trees_close(grads, jax.tree.map(lambda g: g / n, want_grads),
                       rtol=2e-3, atol=2e-7)


def test_three_adamw_steps_match_the_reference(seeded):
    params, batches = seeded
    step = build(remat_policy="ctx")
    p, opt_state = fresh_state(params)
    want = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    for t, (ids, labels) in enumerate(batches, 1):
        loss, p, opt_state, _ = step(p, opt_state, ids, labels)
        want_loss, g = ref_value_and_grad(want, ids, labels)
        np.testing.assert_allclose(loss, want_loss / ids.size, rtol=1e-5)
        want, m, v = ref.adamw(
            want, jax.tree.map(lambda x: x / ids.size, g), m, v, float(t),
            OPT)
    assert_trees_close(p, want, rtol=1e-4, atol=2e-5)


# one window layer and the full layer: the step through the kernels
PAIR = {**TINY, "num_hidden_layers": 2,
        "layer_types": ["sliding_attention", "full_attention"]}


def test_loss_and_gradients_through_the_interpreted_kernels(monkeypatch):
    """The step's loss and every gradient leaf against the reference with
    ``PADDLE_PALLAS_FORCE=1``: the window layer's attention runs the
    resident pair over its band (``stream_resident_window``), the full
    layer's at T = 256 the small regime's kernels, interpreted."""
    from paddle_tpu.ops import pallas
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    params = ref.init_params(PAIR, 7)
    ids, labels = map(jnp.asarray, ref.make_batches(PAIR, TRAFFIC, 7)[0])
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p, i, l: ref.summed_loss(p, i, l, PAIR)))(params, ids, labels)
    before = dict(pallas.selections())
    loss, _p, opt_state, _ = build(PAIR, remat_policy="ctx")(
        *fresh_state(params), ids, labels)
    took = {k for k, v in pallas.selections().items()
            if v != before.get(k, 0)}
    assert {"flash_attention.stream_resident_window.interpret",
            "flash_attention.small.interpret"} <= took, took
    np.testing.assert_allclose(loss, want_loss / ids.size, rtol=1e-5)
    grads = jax.tree.map(lambda m: m / (1 - OPT["beta1"]), opt_state["m"])
    assert_trees_close(
        grads, jax.tree.map(lambda g: g / ids.size, want_grads),
        rtol=2e-3, atol=2e-7)


# ---------------------------------------------------------------------------
# YaRN, by hand
# ---------------------------------------------------------------------------
def test_yarn_frequencies_and_factor_against_the_formula():
    """At the published theta, factor, original length and betas: the
    correction range is [18, 35]; below it the frequencies are RoPE's,
    above it RoPE's over 16, between them a linear blend; the factor on
    cos and sin is 0.1 ln 16 + 1.  The program's angles and the model's
    rotation against numpy by hand."""
    hd, theta = 128, 500000.0
    c = lambda r: hd * math.log(8192 / (2 * math.pi * r)) \
        / (2 * math.log(theta))                            # noqa: E731
    lo, hi = math.floor(c(32)), math.ceil(c(1))
    assert (lo, hi) == (18, 35)
    i = np.arange(hd // 2)
    plain = theta ** (-2.0 * i / hd)
    e = 1 - np.clip((i - lo) / (hi - lo), 0, 1)
    want = plain / 16 * (1 - e) + plain * e
    ang = rope_angles(8192, theta, hd, (16.0, 8192, 32.0, 1.0))
    np.testing.assert_allclose(ang[1], want, rtol=1e-12)
    np.testing.assert_allclose(ang[1][:19], plain[:19], rtol=1e-12)
    np.testing.assert_allclose(ang[1][35:], plain[35:] / 16, rtol=1e-12)
    assert np.all(np.diff(ang[1]) < 0)
    np.testing.assert_allclose(ang[8191], 8191 * want, rtol=1e-12)
    np.testing.assert_allclose(rope_angles(16, theta, hd)[1], plain,
                               rtol=1e-12)
    # the reference's own, written out from the formula
    inv, scale = ref.inv_freq({"head_dim": hd,
                               "rope_parameters": PUBLISHED_ROPE},
                              "full_attention")
    np.testing.assert_allclose(inv, want, rtol=1e-12)
    assert scale == pytest.approx(0.1 * math.log(16) + 1, rel=1e-15)
    # the model's rotation: x (1, T, hd), one head of q and of k one-hot
    # in component 0, turns into (cos, 0 .., sin ..) times the factor
    cfg = Mellum2Config()
    x = jnp.zeros((1, 8192, hd)).at[..., 0].set(1.0)
    for window, inv_i, f in ((1024, plain, 1.0), (None, want, scale)):
        ang, factor = model._rope(cfg, 8192, window)
        assert factor == f
        y = np.asarray(rope_to_heads(x, x, ang, factor)[0])[0, 0]
        t = np.arange(8192)
        np.testing.assert_allclose(y[:, 0], f * np.cos(t * inv_i[0]),
                                   atol=2e-6)
        np.testing.assert_allclose(y[:, hd // 2], f * np.sin(t * inv_i[0]),
                                   atol=2e-6)
        assert not np.any(y[:, 1:hd // 2]) and not np.any(y[:, hd // 2 + 1:])
        x40 = jnp.zeros((1, 8192, hd)).at[..., 40].set(1.0)
        z = np.asarray(rope_to_heads(x40, x40, ang, factor)[0])[0, 0]
        np.testing.assert_allclose(z[:, 40], f * np.cos(t * inv_i[40]),
                                   atol=2e-6)


# ---------------------------------------------------------------------------
# the expert layer: the shares, the exchange, the meshes
# ---------------------------------------------------------------------------
def _expert_layer(seed, D=16, F=8, E=8, held=8, N=(2, 24)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, *s, std=0.3: jax.random.normal(   # noqa: E731
        k, s, jnp.float32) * std
    return {"x": normal(ks[0], *N, D, std=1.0),
            "router_w": normal(ks[1], D, E, std=1.0),
            "w1": normal(ks[3], held, D, F), "w3": normal(ks[4], held, D, F),
            "w2": normal(ks[5], held, F, D)}


def test_the_eight_shares_of_64_experts_are_the_layer():
    """64 experts in 8 shares of 8 (expert parallel 8, the cell's
    deployment), top 8: the routed parts of all shares equal the uncut
    reference's whole layer — no shared expert to count once — and every
    assignment is somebody's."""
    p = _expert_layer(2, E=64, held=64)
    c = {"num_experts_per_tok": 8, "num_experts": 64,
         "deployment": {"router_width": 64, "first_expert": 0}}
    whole = ref.routed_part(p, p["x"], c, MM)[0]
    total, served = jnp.zeros_like(whole), 0
    for first in range(0, 64, 8):
        share = dict(p, **{w: p[w][first:first + 8]
                           for w in ("w1", "w3", "w2")})
        y, counts, overflow = routed_experts(
            p["x"], p["router_w"], None, share["w1"], share["w3"],
            share["w2"], top_k=8, first_expert=first,
            routing=functools.partial(softmax_topk_routing, top_k=8))
        cs = dict(c, num_experts=8,
                  deployment={"router_width": 64, "first_expert": first})
        np.testing.assert_allclose(y, ref.routed_part(share, p["x"], cs,
                                                      MM)[0],
                                   rtol=1e-4, atol=1e-6)
        total, served = total + y, served + int(counts.sum())
        assert int(overflow) == 0
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-6)
    assert served == 8 * p["x"].shape[0] * p["x"].shape[1]
    # and the model's layer is the share plus the residual, nothing more
    cfg = Mellum2Config(hidden_size=16, num_experts=64,
                        num_experts_per_tok=8, num_experts_held=8,
                        moe_intermediate_size=8)
    layer = dict(p, ffn_norm=jnp.ones((16,)),
                 **{w: p[w][:8] for w in ("w1", "w3", "w2")})
    y, *_ = model._expert_ffn(layer, p["x"], cfg, one_device(), None)
    z = ref._norm(p["x"], layer["ffn_norm"], EPS)
    cs = dict(c, num_experts=8)
    np.testing.assert_allclose(y - p["x"], ref.routed_part(layer, z, cs,
                                                           MM)[0],
                               rtol=1e-4, atol=1e-5)


def test_the_step_over_dp_and_ep_matches_one_device(seeded):
    params, batches = seeded
    c = dict(TINY, deployment={"router_width": 4, "first_expert": 0})
    ids = jnp.concatenate([b[0] for b in batches[:2]] * 2)     # batch 8
    labels = jnp.concatenate([b[1] for b in batches[:2]] * 2)
    params = jax.tree.map(lambda a: a, params)
    for p in params["layers"]:
        p["router_w"] = p["router_w"][:, :4]
    want = build(c)(*fresh_state(params), ids, labels)
    mesh = build_mesh({"dp": 2, "ep": 4}, devices=jax.devices()[:8])
    got = build(c, mesh)(*fresh_state(params), ids, labels)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    assert_trees_close(got[2]["m"], want[2]["m"], rtol=2e-3, atol=1e-7)
    assert got[3]["moe_counts"].tolist() == want[3]["moe_counts"].tolist()


@pytest.mark.parametrize("axis", ["pp", "sp", "mp"])
def test_meshes_the_model_has_no_path_for_are_refused(axis):
    mesh = build_mesh({"dp": 2, axis: 2}, devices=jax.devices()[:4])
    with pytest.raises(NotImplementedError, match=f"Mellum2.*{axis}"):
        build(TINY, mesh)


# ---------------------------------------------------------------------------
# the window in the flash kernels
# ---------------------------------------------------------------------------
def _masked(q, k, v, window):
    """(BH, T, d) attention with the window as an explicit mask, float32
    at highest precision."""
    T, Tk = q.shape[1], k.shape[1]
    s = jnp.einsum("bqd,bkd->bqk", q, k,
                   precision=ref.HIGHEST) / np.sqrt(q.shape[-1])
    back = (np.arange(T)[:, None] + Tk - T) - np.arange(Tk)[None, :]
    keep = (back >= 0) & (back < (Tk if window is None else window))
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
    return jnp.einsum("bqk,bkd->bqd", p, v, precision=ref.HIGHEST)


# (T, Tk, block_q, chunk, window): a window smaller than a block and than
# a chunk, windows no multiple of the chunk, one that reaches every key
# (causal), more keys than queries
WINDOW_CASES = [
    (512, 512, 256, 256, 64), (512, 512, 128, 128, 100),
    (512, 512, 256, 128, 300), (384, 384, 128, 128, 1),
    (256, 256, 128, 128, 256), (256, 512, 128, 128, 200),
    (256, 640, 128, 128, 130)]


@pytest.mark.parametrize("T,Tk,bq,ck,window", WINDOW_CASES)
def test_the_window_kernels_against_the_masked_math(T, Tk, bq, ck, window):
    """out, lse, dq, dk, dv of the resident pair run with a window, in
    interpret mode at blocks that put several q blocks and key chunks in
    a row, against the masked math and its ``jax.vjp``."""
    rs = np.random.RandomState(T + window)
    d = 32
    q, g = (jnp.asarray(rs.randn(2, T, d), jnp.float32) for _ in range(2))
    k, v = (jnp.asarray(rs.randn(2, Tk, d), jnp.float32) for _ in range(2))
    plan = fa._Plan("stream_resident", True, (bq, ck, 1), (bq, ck, 1),
                    window=window)
    scale = 1.0 / np.sqrt(d)
    out, lse = fa._stream_flash_fwd(q, k, v, scale, True, plan)
    grads = fa._resident_flash_bwd(q, k, v, out, lse, g, scale, True, plan)
    want, vjp = jax.vjp(lambda a, b, c: _masked(a, b, c, window), q, k, v)
    for name, got, w in zip(("out", "dq", "dk", "dv"), (out, *grads),
                            (want, *vjp(g))):
        np.testing.assert_allclose(got, w, atol=2e-5, rtol=2e-5,
                                   err_msg=name)
    # and the XLA math takes the same mask
    np.testing.assert_allclose(
        fa._xla_attention(q, k, v, scale, True, window), want, atol=2e-5)


@pytest.mark.parametrize("T,Tk,window", [(256, 256, 64), (256, 384, 100),
                                         (256, 256, 256), (128, 384, 400)],
                         ids=["window", "more-keys", "window-is-T",
                              "window-beyond-Tk"])
def test_a_windowed_call_through_the_public_entry(monkeypatch, T, Tk,
                                                  window):
    """``flash_attention(..., window=)`` on (B, T, H, d) arrays: the
    resident pair at any length (``stream_resident_window``); a window
    that reaches every key is a causal call and counts as one."""
    from paddle_tpu.ops import pallas
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    rs = np.random.RandomState(T + Tk)
    q = jnp.asarray(rs.randn(1, T, 2, 32), jnp.float32)
    k, v = (jnp.asarray(rs.randn(1, Tk, 2, 32), jnp.float32)
            for _ in range(2))
    before = dict(pallas.selections())
    out, vjp = jax.vjp(functools.partial(fa.flash_attention, causal=True,
                                         window=window), q, k, v)
    took = {k for k, v in pallas.selections().items()
            if v != before.get(k, 0)}
    fold = lambda x: jnp.swapaxes(x, 1, 2).reshape(2, -1, 32)  # noqa: E731
    want, want_vjp = jax.vjp(
        lambda a, b, c: _masked(fold(a), fold(b), fold(c),
                                window if window < Tk else None), q, k, v)
    np.testing.assert_allclose(fold(out), want, atol=2e-5)
    for got, w in zip(vjp(out), want_vjp(fold(out))):
        np.testing.assert_allclose(got, w, atol=2e-5)
    windowed = "flash_attention.stream_resident_window.interpret" in took
    assert windowed == (window < Tk), took


def test_a_window_is_causal_and_at_least_one():
    q = jnp.zeros((1, 128, 1, 32))
    for kw in ({"causal": False, "window": 16}, {"causal": True,
                                                 "window": 0}):
        with pytest.raises(ValueError, match="window"):
            fa.flash_attention(q, q, q, **kw)


def test_live_chunks_with_a_window_against_the_mask():
    """The loop bounds of the resident kernels for every q block of a
    small grid of (block_q, chunk, offset, window): chunks [n_clean,
    n_full) hold no masked score, chunks outside [n_lo, n_live) no live
    one, and every chunk of [n_lo, n_clean) and [n_full, n_live) holds
    both; the three ranges tile [n_lo, n_live)."""
    checked = 0
    for block_q in (1, 2, 3, 4, 8):
        for chunk in (1, 2, 3, 4, 8):
            for nq in (1, 2, 5):
                for offset in (0, 1, 3, 8):
                    for window in (1, 2, 3, 5, 8, 13):
                        seq_q = nq * block_q
                        seq_k = seq_q + offset
                        if seq_k % chunk:
                            continue
                        nk = seq_k // chunk
                        back = (np.arange(seq_q)[:, None] + offset
                                - np.arange(seq_k)[None, :])
                        mask = (back >= 0) & (back < window)
                        for qi in range(nq):
                            n_lo, n_clean, n_full, n_live = (
                                int(n) for n in fa._live_chunks(
                                    qi, block_q, chunk, offset, nk,
                                    window=window))
                            rows = mask[qi * block_q:(qi + 1) * block_q]
                            tiles = [rows[:, j * chunk:(j + 1) * chunk]
                                     for j in range(nk)]
                            assert 0 <= n_lo <= n_clean <= n_full \
                                <= n_live <= nk
                            assert not any(t.any() for t in tiles[:n_lo])
                            assert not any(t.any() for t in tiles[n_live:])
                            assert all(t.all()
                                       for t in tiles[n_clean:n_full])
                            assert all(t.any() and not t.all()
                                       for t in tiles[n_lo:n_clean]
                                       + tiles[n_full:n_live])
                            checked += 1
    assert checked > 1500
    # no window: the lower edge is column 0, as it always was
    assert [int(n) for n in fa._live_chunks(3, 4, 2, 0, 8)] == [0, 0, 6, 8]


@pytest.mark.parametrize("T,cap,name,fwd,bwd", [
    (8192, 128 << 20, "stream_resident", (1024, 1024, 1), (512, 512, 1)),
    (256, 128 << 20, "stream_resident", (256, 256, 1), (256, 256, 1)),
    (2048, 128 << 20, "stream_resident", (1024, 1024, 1), (512, 512, 1)),
    (65536, 128 << 20, "xla", (None, None, 1), (None, None, 1)),
    (8192, 16 << 20, "xla", (None, None, 1), (None, None, 1))],
    ids=["the-cell", "small", "mid", "too-long", "small-vmem"])
def test_the_plan_of_a_windowed_call(monkeypatch, T, cap, name, fwd, bwd):
    """A window takes the resident pair at any length its budget fits —
    not the small or mid kernels, which take whole rows — else the XLA
    math; the window rides the plan to the backward."""
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    monkeypatch.setattr(fa, "_vmem_capacity", lambda: cap)
    plan = fa._plan("folded", 4, T, T, 32, 128, 2, True, 128, 1024)
    assert (plan.name, plan.fwd, plan.bwd, plan.window) \
        == (name, fwd, bwd, 1024)
    # the cell's plan is the full layer's but for the window
    if T == 8192 and name != "xla":
        full = fa._plan("folded", 4, T, T, 32, 128, 2, True, 128)
        assert plan == full._replace(window=1024)


def test_the_fused_loss_head_at_the_cell_s_hidden_size(monkeypatch):
    """D = 2304 is no power of two: the row block is 256 (1 << 20 // D =
    455 rounded down), not what halving 455 ends in — 1 row, which the
    TPU compiler refuses — and the head is right."""
    from paddle_tpu.ops.pallas import softmax_xent
    grids = []
    real = softmax_xent.pl.pallas_call
    monkeypatch.setattr(softmax_xent.pl, "pallas_call",
                        lambda *a, **kw: grids.append(kw["grid"])
                        or real(*a, **kw))
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(512, 2304), jnp.float32) * 0.05
    w = jnp.asarray(rs.randn(2304, 640), jnp.float32) * 0.05
    labels = jnp.asarray(rs.randint(0, 600, 512), jnp.int32)
    lse, at = softmax_xent.softmax_xent_fwd(x, w, labels, interpret=True)
    assert grids == [(2, 2)]                 # 512 rows in blocks of 256
    s = jnp.dot(x, w, precision=ref.HIGHEST)
    np.testing.assert_allclose(lse, jax.nn.logsumexp(s, -1), rtol=1e-5)
    np.testing.assert_allclose(at, s[np.arange(512), labels], rtol=1e-5,
                               atol=1e-6)
