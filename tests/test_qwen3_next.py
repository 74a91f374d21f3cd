"""Qwen3-Next through ``build_spmd_train_step`` against the plain
reference (``benchmark/references/qwen3_next.py``: the delta rule token
by token, the experts one at a time).

Float32 on the CPU at tiny widths with one whole period (three delta-rule
layers, one gated-attention layer, every FFN the expert layer with its
shared expert); the seeded weights are the reference's own, so one tree
serves both sides.  Compared element-wise: each block kind, the logits,
the loss, every gradient leaf of the first step, the parameters after
three AdamW steps.  Then the parts by themselves: partial RoPE and the
output gate against a hand-written case, the softmax routing against
``jax.nn.softmax`` + ``lax.top_k``, the sixteen shares of 64 experts plus
the shared expert once against the uncut layer, ``ep`` = 4 over a CPU
mesh against one device, and the loss head at a vocabulary that no block
divides.

Tolerances: float32 sums in another order (a chunk's writes at once, a
sort in front of the grouped matmuls) are good to 1e-4 of a value; a
gradient leaf to 2e-3 of it with a floor of 1e-6 of the loss's scale;
after three AdamW steps every weight has moved by about the rate whatever
its gradient's size, so parameters agree to 2e-5 absolute.
"""
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.drivers.qwen3_next_train import model_config as config_of
from benchmark.references import qwen3_next as ref
from paddle_tpu.distributed.fleet.meta_parallel.moe import (
    routed_experts, softmax_topk_routing)
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.models import Qwen3NextConfig
from paddle_tpu.models import qwen3_next as model
from paddle_tpu.models.gpt_spmd import build_spmd_train_step

OPT = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
       "weight_decay": 0.01}
TINY = {
    "hidden_size": 32, "vocab_size": 50, "num_hidden_layers": 4,
    "full_attention_interval": 4, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "partial_rotary_factor": 0.25,
    "rope_theta": 1e7, "rms_norm_eps": 1e-6, "linear_num_key_heads": 2,
    "linear_num_value_heads": 4, "linear_key_head_dim": 8,
    "linear_value_head_dim": 8, "linear_conv_kernel_dim": 4,
    "num_experts": 4, "num_experts_per_tok": 3, "moe_intermediate_size": 16,
    "shared_expert_intermediate_size": 16, "norm_topk_prob": True,
    "deployment": {"router_width": 16, "first_expert": 4},
    "assumed": {"optimizer": OPT, "gdn_chunk": 8}}
# T = 20 is not a multiple of the chunk of 8: the rule pads its tail
TRAFFIC = {"batch": 2, "seq_len": 20, "pool": 3, "check_steps": 3}
MM = functools.partial(jnp.einsum, precision=ref.HIGHEST)


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
    0 and 1 so that a gain that is dropped or centred wrongly shows."""
    params = ref.init_params(TINY, 5)
    ks = iter(jax.random.split(jax.random.PRNGKey(6), 64))
    for p in params["layers"]:
        for name in ("op_norm", "ffn_norm", "q_norm", "k_norm", "gdn_norm"):
            if name in p:
                p[name] = p[name] + 0.1 * jax.random.normal(
                    next(ks), p[name].shape)
    batches = [(jnp.asarray(i), jnp.asarray(l))
               for i, l in ref.make_batches(TINY, TRAFFIC, 5)]
    return params, batches


def test_the_models_package_exports_the_configuration():
    cfg = config_of(TINY)
    assert isinstance(cfg, Qwen3NextConfig)
    assert [cfg.is_attention(l) for l in range(4)] == [False] * 3 + [True]
    assert (cfg.held, cfg.num_experts, cfg.first_expert) == (4, 16, 4)
    # the published sizes are the defaults
    full = Qwen3NextConfig()
    assert (full.num_layers, full.num_experts, full.head_dim) == (48, 512, 256)
    assert sum(full.is_attention(l) for l in range(48)) == 12


# ---------------------------------------------------------------------------
# each block kind against the reference's
# ---------------------------------------------------------------------------
def _x(seed, T=20):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, T, 32))


def _kinds():
    cfg, mesh = config_of(TINY), one_device()
    eps = TINY["rms_norm_eps"]

    def ref_ffn(p, x):
        z = ref._norm(x, p["ffn_norm"], eps)
        return x + ref.routed_part(p, z, TINY, MM)[0] \
            + ref.shared_part(p, z, MM)

    return {
        "delta-rule": (
            0, lambda p, x: model._gated_delta_net(p, x, cfg, mesh, ()),
            lambda p, x: x + ref.delta_op(
                p, ref._norm(x, p["op_norm"], eps), TINY, MM)),
        "gated-attention": (
            3, lambda p, x: model._gated_attention(p, x, cfg, mesh, ()),
            lambda p, x: x + ref.attention_op(
                p, ref._norm(x, p["op_norm"], eps), TINY, MM)),
        "experts-and-shared": (
            1, lambda p, x: model._expert_ffn(p, x, cfg, mesh, None)[0],
            ref_ffn)}


@pytest.mark.parametrize("kind", ["delta-rule", "gated-attention",
                                  "experts-and-shared"])
def test_a_block_kind_matches_the_reference(seeded, kind):
    layer, got, want = _kinds()[kind]
    p, x = seeded[0]["layers"][layer], _x(1)
    np.testing.assert_allclose(jax.jit(got)(p, x), jax.jit(want)(p, x),
                               rtol=1e-4, atol=1e-5)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))   # noqa: E731
    assert_trees_close(jax.jit(jax.grad(loss(got), (0, 1)))(p, x),
                       jax.jit(jax.grad(loss(want), (0, 1)))(p, x),
                       rtol=2e-3, atol=2e-5)


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
    # the decay's two float32 parameters and every router are trained
    for l, layer in enumerate(grads["layers"]):
        assert np.any(np.asarray(layer["router_w"]))
        if l < 3:
            assert np.any(np.asarray(layer["A_log"]))
            assert np.any(np.asarray(layer["dt_bias"]))


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
    assert np.any(np.asarray(p["layers"][0]["A_log"]
                             != params["layers"][0]["A_log"]))


# ---------------------------------------------------------------------------
# the mixer's kernels, interpreted: heads of 128 columns, where they tile
# ---------------------------------------------------------------------------
# one delta-rule layer and one gated-attention layer; a key head of 128
# under two value heads; T = 32 is two chunks of 16 and two 16-row tiles
WIDE = {**TINY, "num_hidden_layers": 2, "full_attention_interval": 2,
        "linear_num_key_heads": 1, "linear_num_value_heads": 2,
        "linear_key_head_dim": 128, "linear_value_head_dim": 128,
        "assumed": {"optimizer": OPT, "gdn_chunk": 16}}
WIDE_TRAFFIC = {"batch": 2, "seq_len": 32, "pool": 1, "check_steps": 1}


@pytest.mark.parametrize("force,impl", [("0", "xla"), ("1", "interpret")],
                         ids=["xla-math", "kernels-forced"])
def test_loss_and_gradients_with_the_mixer_s_kernels(monkeypatch, force,
                                                     impl):
    """The step's loss and every gradient leaf against the reference, on
    the XLA path and with ``PADDLE_PALLAS_FORCE=1`` — where the
    convolution (``gdn_conv_fwd`` twice under ``ctx``, ``gdn_conv_bwd``)
    and the rule run as their Pallas kernels, interpreted — within the
    same tolerances."""
    from paddle_tpu.ops import pallas
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", force)
    params = ref.init_params(WIDE, 7)
    ids, labels = map(jnp.asarray, ref.make_batches(WIDE, WIDE_TRAFFIC,
                                                    7)[0])
    want_loss, want_grads = jax.jit(jax.value_and_grad(
        lambda p, i, l: ref.summed_loss(p, i, l, WIDE)))(params, ids, labels)
    before = dict(pallas.selections())
    loss, _p, opt_state, _ = build(WIDE, remat_policy="ctx")(
        *fresh_state(params), ids, labels)
    took = {k for k, v in pallas.selections().items()
            if v != before.get(k, 0)}
    assert {f"causal_conv.{impl}", f"gated_delta_rule.{impl}"} <= took, took
    np.testing.assert_allclose(loss, want_loss / ids.size, rtol=1e-5)
    grads = jax.tree.map(lambda m: m / (1 - OPT["beta1"]), opt_state["m"])
    assert_trees_close(
        grads, jax.tree.map(lambda g: g / ids.size, want_grads),
        rtol=2e-3, atol=2e-7)
    assert np.any(np.asarray(grads["layers"][0]["conv_w"]))


# ---------------------------------------------------------------------------
# partial RoPE and the output gate, by hand
# ---------------------------------------------------------------------------
def test_partial_rope_rotates_a_quarter_of_the_head():
    """head size 16, a quarter rotated: components (0, 2) and (1, 3) are
    pairs with frequencies 1 and theta^(-1/2); 4..15 pass untouched."""
    x = jax.random.normal(jax.random.PRNGKey(0), (1, 5, 2, 16))
    got = np.asarray(model._partial_rope(x, 100.0, 4))
    x = np.asarray(x)
    want = x.copy()
    for t in range(5):
        for i, freq in enumerate((1.0, 100.0 ** -0.5)):
            c, s = np.cos(t * freq), np.sin(t * freq)
            want[0, t, :, i] = x[0, t, :, i] * c - x[0, t, :, i + 2] * s
            want[0, t, :, i + 2] = x[0, t, :, i + 2] * c + x[0, t, :, i] * s
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_array_equal(got[..., 4:], x[..., 4:])
    np.testing.assert_allclose(np.asarray(ref.partial_rope(
        jnp.asarray(x), 100.0, 4)), want, rtol=1e-5, atol=1e-6)


def test_the_gate_is_the_second_half_of_each_head_s_columns(seeded):
    """One token attends to itself alone: ctx = v, so the layer is
    ``x + (v * sigmoid(gate)) W_o`` with the gate read from columns
    ``h 2 hd + hd ..`` of ``z W_q`` and v from KV head ``h // 2``."""
    cfg = config_of(TINY)
    p = seeded[0]["layers"][3]
    x = _x(2, T=1)
    got = np.asarray(model._gated_attention(p, x, cfg, one_device(), ()))
    z = np.asarray(ref._norm(x, p["op_norm"], 1e-6))
    qg = (z @ np.asarray(p["q_w"])).reshape(2, 1, 4, 2, 16)
    v = (z @ np.asarray(p["v_w"])).reshape(2, 1, 2, 16)
    ctx = v[:, :, [0, 0, 1, 1]] / (1 + np.exp(-qg[..., 1, :]))
    want = np.asarray(x) + ctx.reshape(2, 1, 64) @ np.asarray(p["o_w"])
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)


def test_gated_attention_through_the_interpreted_kernel(seeded, monkeypatch):
    """T = 128 under ``PADDLE_PALLAS_FORCE`` takes the small-T kernels,
    interpreted, at a head size that is no 32, 64 or 128."""
    from paddle_tpu.ops import pallas
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    layer, got, want = _kinds()["gated-attention"]
    p, x = seeded[0]["layers"][layer], _x(3, T=128)
    before = dict(pallas.selections())
    np.testing.assert_allclose(jax.jit(got)(p, x), jax.jit(want)(p, x),
                               rtol=1e-4, atol=1e-5)
    took = {k for k, v in pallas.selections().items()
            if v != before.get(k, 0)}
    assert took == {"flash_attention.small.interpret"}


# ---------------------------------------------------------------------------
# the expert layer: the routing, the shares, the exchange
# ---------------------------------------------------------------------------
def _expert_layer(seed, D=16, F=8, E=8, held=8, N=(2, 24)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    normal = lambda k, *s, std=0.3: jax.random.normal(   # noqa: E731
        k, s, jnp.float32) * std
    return {"x": normal(ks[0], *N, D, std=1.0),
            "router_w": normal(ks[1], D, E, std=1.0),
            "w1": normal(ks[3], held, D, F), "w3": normal(ks[4], held, D, F),
            "w2": normal(ks[5], held, F, D),
            "shared_w1": normal(ks[6], D, F), "shared_w3": normal(ks[7], D, F),
            "shared_w2": normal(ks[8], F, D),
            "shared_gate_w": normal(ks[9], D, 1)}


def _program_routed(p, first, k, **kw):
    return routed_experts(
        p["x"], p["router_w"], None, p["w1"], p["w3"], p["w2"], top_k=k,
        first_expert=first,
        routing=functools.partial(softmax_topk_routing, top_k=k), **kw)


def _reference_routed(p, first, k, width):
    c = {"num_experts_per_tok": k, "num_experts": p["w1"].shape[0],
         "deployment": {"router_width": width, "first_expert": first}}
    return ref.routed_part(p, p["x"], c, MM)[0]


@pytest.mark.parametrize("renormalize", [True, False],
                         ids=["norm_topk_prob", "raw-probabilities"])
def test_softmax_routing_against_softmax_and_top_k(renormalize):
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    z = jax.random.normal(ks[0], (40, 16))
    w_r = jax.random.normal(ks[1], (16, 64))
    idx, w = softmax_topk_routing(z, w_r, top_k=5, renormalize=renormalize)
    probs = jax.nn.softmax(jnp.dot(z, w_r, precision=lax.Precision.HIGHEST))
    want_w, want_idx = lax.top_k(probs, 5)
    if renormalize:
        want_w = want_w / want_w.sum(-1, keepdims=True)
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, rtol=1e-6)
    if renormalize:
        np.testing.assert_allclose(w.sum(-1), 1.0, rtol=1e-6)
    # differentiable through the chosen probabilities
    g = jax.grad(lambda w_r: jnp.sum(jnp.sin(softmax_topk_routing(
        z, w_r, top_k=5, renormalize=renormalize)[1])))(w_r)
    want_g = jax.grad(lambda w_r: jnp.sum(jnp.sin((
        lambda t: t / t.sum(-1, keepdims=True) if renormalize else t)(
        lax.top_k(jax.nn.softmax(jnp.dot(
            z, w_r, precision=lax.Precision.HIGHEST)), 5)[0]))))(w_r)
    np.testing.assert_allclose(g, want_g, rtol=2e-3, atol=1e-6)


def test_softmax_routing_has_no_selection_bias():
    z, w_r = jnp.ones((4, 8)), jnp.ones((8, 16))
    with pytest.raises(AssertionError, match="bias"):
        softmax_topk_routing(z, w_r, jnp.zeros((16,)), top_k=2)


def test_the_default_routing_is_still_the_sigmoid_one():
    """LFM2's call, which names no routing, and the same call with the
    routing handed in are one program."""
    from paddle_tpu.distributed.fleet.meta_parallel.moe import (
        sigmoid_topk_routing)
    p = _expert_layer(4)
    bias = 0.01 * jax.random.normal(jax.random.PRNGKey(1), (8,))
    args = (p["x"], p["router_w"], bias, p["w1"], p["w3"], p["w2"])
    want = routed_experts(*args, top_k=2, scaling=1.5)
    got = routed_experts(*args, top_k=2, routing=lambda z, w, b:
                         sigmoid_topk_routing(z, w, b, 2, 1.5))
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)


def test_the_sixteen_shares_and_the_shared_expert_once_are_the_layer():
    """64 experts in 16 shares of 4: the routed parts of all shares plus
    the shared expert ONCE equal the uncut reference's whole layer, and
    every assignment is somebody's."""
    p = _expert_layer(2, E=64, held=64)
    whole = _reference_routed(p, 0, 6, 64) + ref.shared_part(p, p["x"], MM)
    total, served = jnp.zeros_like(whole), 0
    for first in range(0, 64, 4):
        share = dict(p, **{w: p[w][first:first + 4]
                           for w in ("w1", "w3", "w2")})
        y, counts, overflow = _program_routed(share, first, 6)
        np.testing.assert_allclose(
            y, _reference_routed(share, first, 6, 64), rtol=1e-4, atol=1e-6)
        total, served = total + y, served + int(counts.sum())
        assert int(overflow) == 0
    # what every chip computes alike, counted once: the program's own
    cfg = Qwen3NextConfig(hidden_size=16, num_experts=64,
                          num_experts_per_tok=6, num_experts_held=4)
    layer = dict(p, ffn_norm=jnp.zeros((16,)),
                 **{w: p[w][:4] for w in ("w1", "w3", "w2")})
    x = p["x"]
    with_shared, *_ = model._expert_ffn(layer, x, cfg, one_device(), None)
    z = ref._norm(x, layer["ffn_norm"], cfg.rms_norm_eps)
    first_share, *_ = _program_routed(dict(layer, x=z), 0, 6)
    shared = with_shared - x - first_share
    np.testing.assert_allclose(shared, ref.shared_part(p, z, MM),
                               rtol=1e-4, atol=1e-5)
    np.testing.assert_allclose(
        total + ref.shared_part(p, p["x"], MM), whole, rtol=1e-4, atol=1e-6)
    assert served == 6 * p["x"].shape[0] * p["x"].shape[1]


@pytest.mark.parametrize("dims", [{"ep": 4}, {"dp": 2, "ep": 4}],
                         ids=["ep4", "dp2-ep4"])
def test_softmax_routed_experts_over_ep_equal_the_one_device_layer(dims):
    n = int(np.prod(list(dims.values())))
    mesh = build_mesh(dims, devices=jax.devices()[:n])
    p = _expert_layer(3, N=(8, 6))
    axes = tuple(dims)

    def over_ep(p):
        return _program_routed(p, 0, 3, mesh=mesh, token_axes=axes,
                               ep_axis="ep")

    want_y, want_counts, _ = _program_routed(p, 0, 3)
    y, counts, overflow = jax.jit(over_ep)(p)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-6)
    assert counts.tolist() == want_counts.tolist()
    assert int(overflow) == 0


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
    with pytest.raises(NotImplementedError, match=f"Qwen3-Next.*{axis}"):
        build(TINY, mesh)


# ---------------------------------------------------------------------------
# the loss head at a vocabulary that no block divides
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("V", [1187, 2374],
                         ids=["a-sixteenth-of-18992", "an-eighth"])
def test_the_fused_loss_head_at_a_vocabulary_no_block_divides(V):
    """18 992 = 37 x 512 + 48 is no multiple of the kernel's 512-column
    tile, of 128 lanes or of the backward's row chunk; neither are its
    sixteenth and its eighth.  Interpreted kernel against jnp."""
    from paddle_tpu.ops.pallas import softmax_xent as sx
    assert V % 128 and 18992 % V == 0
    rs = np.random.RandomState(V)
    N, D = 256, 64
    x = jnp.asarray(rs.randn(N, D), jnp.float32)
    w = jnp.asarray(rs.randn(D, V) * 0.05, jnp.float32)
    lab = jnp.asarray(rs.randint(0, V, (N,)), jnp.int32).at[0].set(V - 1)

    def want(x, w):
        logits = jnp.dot(x, w, precision=lax.Precision.HIGHEST)
        return jnp.mean(jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, lab[:, None], 1)[:, 0])

    got = lambda x, w: sx.softmax_xent_loss(x, w, lab, True)   # noqa: E731
    np.testing.assert_allclose(got(x, w), want(x, w), rtol=1e-6)
    for a, b in zip(jax.grad(got, (0, 1))(x, w),
                    jax.grad(want, (0, 1))(x, w)):
        np.testing.assert_allclose(a, b, atol=2e-6)
