"""JoyAI-LLM-Flash through ``build_spmd_train_step`` against the plain
reference (``benchmark/references/joyai_flash.py``: attention one head at
a time over the whole (T, T) scores, the experts one at a time, RoPE as
complex numbers, ``eh_proj`` on the concatenated halves).

Float32 on the CPU at tiny widths: a leading dense layer, two expert
layers and the multi-token-prediction module; the seeded weights are the
reference's own, so one tree serves both sides.  Compared element-wise:
each block kind and the module, the two-term loss, every gradient leaf of
the first step, the parameters after three AdamW steps.  Then the parts
by themselves: interleaved RoPE against complex numbers, the ``noaux_tc``
routing against a hand-written top-k, the four shares of 32 experts plus
the shared expert once against the uncut layer, what the second
prediction depth adds to the shared embedding's and head's gradients,
``ep`` = 4 over a CPU mesh against one device.

Tolerances: float32 sums in another order (a sort in front of the grouped
matmuls, two products for one concat) are good to 1e-4 of a value; a
gradient leaf to 2e-3 of it with a floor of 1e-6 of the loss's scale;
after three AdamW steps every weight has moved by about the rate whatever
its gradient's size, so parameters agree to 2e-5 absolute.
"""
import dataclasses
import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax import lax

from benchmark.drivers.joyai_train import model_config as config_of
from benchmark.references import joyai_flash as ref
from paddle_tpu.distributed.fleet.meta_parallel.moe import (
    routed_experts, sigmoid_topk_routing)
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.models import JoyAIFlashConfig
from paddle_tpu.models import joyai_flash as model
from paddle_tpu.models.gpt_spmd import build_spmd_train_step

OPT = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
       "weight_decay": 0.01}
LAMBDA = 0.3
TINY = {
    "hidden_size": 32, "vocab_size": 50, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_attention_heads": 4,
    "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 8,
    "qk_rope_head_dim": 4, "v_head_dim": 8, "rope_theta": 1e4,
    "rms_norm_eps": 1e-6, "n_routed_experts": 4, "num_experts_per_tok": 3,
    "n_shared_experts": 1, "routed_scaling_factor": 2.5,
    "num_nextn_predict_layers": 1,
    "deployment": {"router_width": 16, "first_expert": 4},
    "assumed": {"optimizer": OPT, "mtp_loss_weight": LAMBDA}}
TRAFFIC = {"batch": 2, "seq_len": 20, "pool": 3, "check_steps": 3}
MM = functools.partial(jnp.einsum, precision=ref.HIGHEST)
EPS = TINY["rms_norm_eps"]


def one_device():
    return build_mesh({"dp": 1}, devices=jax.devices()[:1])


def build(cfg=None, mesh=None, **kw):
    step, _ = build_spmd_train_step(
        cfg or config_of(TINY), mesh or one_device(),
        compute_dtype=jnp.float32, learning_rate=OPT["learning_rate"],
        weight_decay=OPT["weight_decay"], **kw)
    return step


def fresh_state(params):
    zeros = lambda: jax.tree.map(jnp.zeros_like, params)   # noqa: E731
    return (jax.tree.map(jnp.copy, params),
            {"m": zeros(), "v": zeros(), "step": jnp.zeros((), jnp.int32)})


def first_gradient(opt_state):
    """The first gradient as the optimizer got it: m1 = (1 - beta1) g."""
    return jax.tree.map(lambda m: m / (1 - OPT["beta1"]), opt_state["m"])


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
    ks = iter(jax.random.split(jax.random.PRNGKey(6), 64))

    def perturb(p):
        for name in p:
            if name.endswith("_norm"):
                p[name] = p[name] + 0.1 * jax.random.normal(
                    next(ks), p[name].shape)

    for p in (*params["layers"], params["mtp"], params["mtp"]["layer"],
              params):
        perturb(p)
    batches = [(jnp.asarray(i), jnp.asarray(l))
               for i, l in ref.make_batches(TINY, TRAFFIC, 5)]
    return params, batches


def test_the_models_package_exports_the_configuration():
    cfg = config_of(TINY)
    assert isinstance(cfg, JoyAIFlashConfig)
    assert (cfg.held, cfg.n_routed_experts, cfg.first_expert) == (4, 16, 4)
    assert cfg.mtp_loss_weight == LAMBDA
    # the published sizes are the defaults
    full = JoyAIFlashConfig()
    assert (full.num_layers, full.n_routed_experts, full.q_lora_rank,
            full.kv_lora_rank) == (40, 256, 1536, 512)
    assert (full.qk_nope_head_dim + full.qk_rope_head_dim,
            full.v_head_dim) == (192, 128)
    with pytest.raises(NotImplementedError, match="softmax"):
        config_of(dict(TINY, scoring_func="softmax"))


# ---------------------------------------------------------------------------
# each block kind and the module against the reference's
# ---------------------------------------------------------------------------
def _x(seed, T=20):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, T, 32))


def _kinds():
    cfg, mesh = config_of(TINY), one_device()

    def ref_experts(p, x):
        z = ref._norm(x, p["ffn_norm"], EPS)
        return x + ref.routed_part(p, z, TINY, MM)[0] \
            + ref.shared_part(p, z, MM)

    return {
        "mla": (1, lambda p, x: model._mla(p, x, cfg, mesh, ()),
                lambda p, x: x + ref.mla_op(
                    p, ref._norm(x, p["op_norm"], EPS), TINY, MM)),
        "dense-ffn": (
            0, lambda p, x: model.dense_ffn(p, x, EPS),
            lambda p, x: x + ref.swiglu(ref._norm(x, p["ffn_norm"], EPS),
                                        p["w1"], p["w3"], p["w2"], MM)),
        "experts-and-shared": (
            2, lambda p, x: model._expert_ffn(p, x, cfg, mesh, None)[0],
            ref_experts)}


@pytest.mark.parametrize("kind", ["mla", "dense-ffn", "experts-and-shared"])
def test_a_block_kind_matches_the_reference(seeded, kind):
    layer, got, want = _kinds()[kind]
    p, x = seeded[0]["layers"][layer], _x(1)
    np.testing.assert_allclose(jax.jit(got)(p, x), jax.jit(want)(p, x),
                               rtol=1e-4, atol=1e-5)
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))   # noqa: E731
    assert_trees_close(jax.jit(jax.grad(loss(got), (0, 1)))(p, x),
                       jax.jit(jax.grad(loss(want), (0, 1)))(p, x),
                       rtol=2e-3, atol=2e-5)


def test_both_depths_hidden_states_and_counters_match_the_reference(seeded):
    params, batches = seeded
    ids, labels = batches[0]
    parts = config_of(TINY).spmd_parts(one_device())
    x, counters, (depth,) = parts.trunk(params, ids, lambda f: f, labels)
    want_x, want_ahead, chosen = jax.jit(
        lambda p, i, l: ref.hidden_states(p, i, l, TINY))(params, ids,
                                                           labels)
    np.testing.assert_allclose(x, want_x, rtol=2e-4, atol=2e-5)
    np.testing.assert_allclose(depth["hidden"], want_ahead, rtol=2e-4,
                               atol=2e-5)
    # the module's targets are the labels one token on; a row's last
    # position has none
    np.testing.assert_array_equal(depth["labels"][:, :-1], labels[:, 1:])
    assert depth["row_weight"].shape == labels.shape
    assert np.asarray(depth["row_weight"]).tolist() \
        == [[True] * 19 + [False]] * 2
    assert (depth["name"], depth["loss_weight"]) == ("mtp", LAMBDA)
    # what the device counts is what the reference's router chose: two
    # expert layers, then the module's
    assert counters["moe_counts"].shape == (3, 4)
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
    # the two terms are the step's counters
    main, ahead = ref.loss_terms(params, ids, labels, TINY)
    np.testing.assert_allclose(counters["loss_main"], main / n, rtol=1e-5)
    np.testing.assert_allclose(counters["loss_mtp"], ahead / (2 * 19),
                               rtol=1e-5)
    np.testing.assert_allclose(
        loss, counters["loss_main"] + LAMBDA * counters["loss_mtp"],
        rtol=1e-6)
    assert int(counters["moe_overflow"]) == 0
    grads = first_gradient(opt_state)
    assert_trees_close(grads, jax.tree.map(lambda g: g / n, want_grads),
                       rtol=2e-3, atol=2e-7)
    # every router is trained, no selection bias has a gradient
    for layer in (*grads["layers"][1:], grads["mtp"]["layer"]):
        assert np.any(np.asarray(layer["router_w"]))
        assert not np.any(np.asarray(layer["router_bias"]))


def test_three_adamw_steps_match_and_the_selection_bias_stays(seeded):
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
    for got, was in ((p["layers"][1], params["layers"][1]),
                     (p["mtp"]["layer"], params["mtp"]["layer"])):
        np.testing.assert_array_equal(got["router_bias"],
                                      was["router_bias"])
        assert np.any(np.asarray(got["router_w"] != was["router_w"]))


# ---------------------------------------------------------------------------
# interleaved RoPE, by hand
# ---------------------------------------------------------------------------
def test_interleaved_rope_turns_neighbouring_pairs_as_complex_numbers():
    """The rotated part of a head, 4 wide: components (0, 1) and (2, 3)
    are complex numbers turned by t and t theta^(-1/2).  The program
    rotates ``y = z w`` with the help of ``z _pair_swap(w)``, which is i
    times those numbers.  Rotate-half would pair (0, 2) and (1, 3): a
    different vector."""
    ks = jax.random.split(jax.random.PRNGKey(0), 2)
    z = jax.random.normal(ks[0], (2, 5, 16))
    w = jax.random.normal(ks[1], (16, 3, 4))
    y = jnp.einsum("btc,chd->bthd", z, w, precision=ref.HIGHEST)
    swapped = jnp.einsum("btc,chd->bthd", z, model._pair_swap(w),
                         precision=ref.HIGHEST)
    np.testing.assert_allclose(swapped[..., 0::2], -y[..., 1::2], rtol=1e-6)
    np.testing.assert_allclose(swapped[..., 1::2], y[..., 0::2], rtol=1e-6)
    got = np.asarray(model._rotate(y, swapped, 100.0))
    y = np.asarray(y)
    want = np.empty_like(y)
    for t in range(5):
        for i, freq in enumerate((1.0, 100.0 ** -0.5)):
            turned = (y[:, t, :, 2 * i] + 1j * y[:, t, :, 2 * i + 1]) \
                * np.exp(1j * t * freq)
            want[:, t, :, 2 * i], want[:, t, :, 2 * i + 1] = \
                turned.real, turned.imag
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(ref.rope_interleaved(
        jnp.asarray(y), 100.0)), want, rtol=1e-5, atol=1e-6)
    # the other pairing, as Qwen3-Next's model has it, on the same four
    from paddle_tpu.models.qwen3_next import _partial_rope
    half = np.asarray(_partial_rope(jnp.asarray(y), 100.0, 4))
    assert np.abs(half[:, 1:] - want[:, 1:]).max() > 0.1
    # position 0 is not turned, whatever the pairing
    np.testing.assert_allclose(got[:, 0], y[:, 0], atol=1e-6)


def test_the_rotated_key_part_is_shared_by_the_heads(seeded):
    """One token attends to itself alone: ctx = v, so the layer is ``x +
    v W_o`` whatever q and k are — and a change to the key latent's
    rotated columns (the last qk_rope_head_dim of W_kva) moves nothing,
    while at two tokens it moves every head's scores."""
    cfg = config_of(TINY)
    p = seeded[0]["layers"][1]
    x = _x(2, T=1)
    z = np.asarray(ref._norm(x, p["op_norm"], EPS))
    c = (z @ np.asarray(p["kv_a_w"]))[..., :16]
    c_kv = np.asarray(ref._norm(jnp.asarray(c), p["kv_a_norm"], EPS))
    v = (c_kv @ np.asarray(p["kv_b_w"])).reshape(2, 1, 4, 16)[..., 8:]
    want = np.asarray(x) + v.reshape(2, 1, 32) @ np.asarray(p["o_w"])
    got = model._mla(p, x, cfg, one_device(), ())
    np.testing.assert_allclose(got, want, rtol=1e-4, atol=1e-5)
    moved = dict(p, kv_a_w=p["kv_a_w"].at[:, 16:].add(0.5))
    np.testing.assert_allclose(model._mla(moved, x, cfg, one_device(), ()),
                               want, rtol=1e-4, atol=1e-5)
    x2 = _x(2, T=2)
    delta = np.asarray(model._mla(moved, x2, cfg, one_device(), ())
                       - model._mla(p, x2, cfg, one_device(), ()))
    assert np.abs(delta[:, 0]).max() < 1e-5 < np.abs(delta[:, 1]).max()


# ---------------------------------------------------------------------------
# the expert layer: the routing, the shares, the exchange
# ---------------------------------------------------------------------------
def test_noaux_tc_routing_against_a_hand_written_top_k():
    """sigmoid scores, the top-8 of scores + bias, weights s_e / (sum of
    the chosen s + 1e-20) x 2.5: the published gate at one group."""
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    z = jax.random.normal(ks[0], (40, 16))
    w_r = jax.random.normal(ks[1], (16, 64))
    bias = 0.5 * jax.random.normal(ks[2], (64,))
    idx, w = sigmoid_topk_routing(z, w_r, bias, 8, 2.5, 1e-20)
    s = np.asarray(jax.nn.sigmoid(jnp.dot(
        z, w_r, precision=lax.Precision.HIGHEST)), np.float64)
    want_idx = np.argsort(-(s + np.asarray(bias)), axis=-1,
                          kind="stable")[:, :8]
    chosen = np.take_along_axis(s, want_idx, -1)
    want_w = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * 2.5
    assert idx.dtype == jnp.int32 and w.dtype == jnp.float32
    np.testing.assert_array_equal(idx, want_idx)
    np.testing.assert_allclose(w, want_w, rtol=1e-5)
    np.testing.assert_allclose(w.sum(-1), 2.5, rtol=1e-5)
    # the bias selects (it changes who is chosen) and is in no weight
    unbiased, _ = sigmoid_topk_routing(z, w_r, 0 * bias, 8, 2.5, 1e-20)
    assert np.any(np.sort(unbiased, -1) != np.sort(idx, -1))
    g = jax.grad(lambda b: jnp.sum(sigmoid_topk_routing(
        z, w_r, b, 8, 2.5, 1e-20)[1]))(bias)
    assert not np.any(np.asarray(g))


def test_the_epsilon_is_an_argument_and_lfm2_s_call_is_unchanged():
    """1e-6 (the ``lfm2_moe`` code's) stays the default, so LFM2's call,
    which names none, computes what it did; with scores near nought the
    two epsilons give different weights."""
    ks = jax.random.split(jax.random.PRNGKey(1), 3)
    z = jax.random.normal(ks[0], (24, 16))
    w_r = jax.random.normal(ks[1], (16, 32))
    bias = 0.01 * jax.random.normal(ks[2], (32,))
    idx, w = sigmoid_topk_routing(z, w_r, bias, 4, 1.5)
    s = jax.nn.sigmoid(jnp.dot(z, w_r, precision=lax.Precision.HIGHEST))
    chosen = jnp.take_along_axis(s, idx, -1)
    np.testing.assert_array_equal(
        w, chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6) * 1.5)
    for a, b in zip(sigmoid_topk_routing(z, w_r, bias, 4, 1.5, 1e-6),
                    (idx, w)):
        np.testing.assert_array_equal(a, b)
    # scores of 1.3e-14 (logits of -32): 1e-6 in the denominator swamps
    # them, 1e-20 does not
    ones, low = jnp.ones((3, 16)), jnp.full((16, 32), -2.0)
    _, swamped = sigmoid_topk_routing(ones, low, bias, 4)
    _, exact = sigmoid_topk_routing(ones, low, bias, 4, eps=1e-20)
    assert np.asarray(swamped.sum(-1)).max() < 1e-6
    np.testing.assert_allclose(exact.sum(-1), 1.0, rtol=1e-5)


def _expert_layer(seed, D=16, F=8, E=32, N=(2, 24)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 10)
    normal = lambda k, *s, std=0.3: jax.random.normal(   # noqa: E731
        k, s, jnp.float32) * std
    return {"x": normal(ks[0], *N, D, std=1.0),
            "router_w": normal(ks[1], D, E, std=1.0),
            "router_bias": normal(ks[2], E, std=0.3),
            "w1": normal(ks[3], E, D, F), "w3": normal(ks[4], E, D, F),
            "w2": normal(ks[5], E, F, D),
            "shared_w1": normal(ks[6], D, F), "shared_w3": normal(ks[7], D, F),
            "shared_w2": normal(ks[8], F, D)}


def _program_routed(p, first, k, **kw):
    return routed_experts(
        p["x"], p["router_w"], p["router_bias"], p["w1"], p["w3"], p["w2"],
        top_k=k, first_expert=first,
        routing=functools.partial(sigmoid_topk_routing, top_k=k,
                                  scaling=2.5, eps=1e-20), **kw)


def _reference_routed(p, first, k, width):
    c = {"num_experts_per_tok": k, "n_routed_experts": p["w1"].shape[0],
         "routed_scaling_factor": 2.5,
         "deployment": {"router_width": width, "first_expert": first}}
    return ref.routed_part(p, p["x"], c, MM)[0]


def test_the_four_shares_and_the_shared_expert_once_are_the_layer():
    """32 experts in 4 shares of 8: the routed parts of all shares plus
    the shared expert ONCE equal the uncut reference's whole layer, and
    every assignment is somebody's."""
    p = _expert_layer(2)
    whole = _reference_routed(p, 0, 6, 32) + ref.shared_part(p, p["x"], MM)
    total, served = jnp.zeros_like(whole), 0
    for first in range(0, 32, 8):
        share = dict(p, **{w: p[w][first:first + 8]
                           for w in ("w1", "w3", "w2")})
        y, counts, overflow = _program_routed(share, first, 6)
        np.testing.assert_allclose(
            y, _reference_routed(share, first, 6, 32), rtol=1e-4, atol=1e-6)
        total, served = total + y, served + int(counts.sum())
        assert int(overflow) == 0
    # what every chip computes alike, counted once: the program's own
    cfg = JoyAIFlashConfig(hidden_size=16, n_routed_experts=32,
                           num_experts_per_tok=6, num_experts_held=8)
    layer = dict(p, ffn_norm=jnp.ones((16,)),
                 **{w: p[w][:8] for w in ("w1", "w3", "w2")})
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
def test_routed_experts_over_ep_equal_the_one_device_layer(dims):
    n = int(np.prod(list(dims.values())))
    mesh = build_mesh(dims, devices=jax.devices()[:n])
    p = _expert_layer(3, E=8, N=(8, 6))
    axes = tuple(dims)

    def over_ep(p):
        return _program_routed(p, 0, 3, mesh=mesh, token_axes=axes,
                               ep_axis="ep")

    want_y, want_counts, _ = _program_routed(p, 0, 3)
    y, counts, overflow = jax.jit(over_ep)(p)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-6)
    assert counts.tolist() == want_counts.tolist()
    assert int(overflow) == 0


def _whole_router(params):
    """The seeded weights for a deployment that holds every expert: the
    router cut to the four held."""
    params = jax.tree.map(lambda a: a, params)
    for p in (*params["layers"][1:], params["mtp"]["layer"]):
        p["router_w"] = p["router_w"][:, :4]
        p["router_bias"] = p["router_bias"][:4]
    return params


def test_the_step_over_dp_and_ep_matches_one_device(seeded):
    params, batches = seeded
    c = dict(TINY, deployment={"router_width": 4, "first_expert": 0})
    ids = jnp.concatenate([b[0] for b in batches[:2]] * 2)     # batch 8
    labels = jnp.concatenate([b[1] for b in batches[:2]] * 2)
    params = _whole_router(params)
    want = build(config_of(c))(*fresh_state(params), ids, labels)
    mesh = build_mesh({"dp": 2, "ep": 4}, devices=jax.devices()[:8])
    got = build(config_of(c), mesh)(*fresh_state(params), ids, labels)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    assert_trees_close(got[2]["m"], want[2]["m"], rtol=2e-3, atol=1e-7)
    assert got[3]["moe_counts"].tolist() == want[3]["moe_counts"].tolist()
    np.testing.assert_allclose(got[3]["loss_mtp"], want[3]["loss_mtp"],
                               rtol=1e-5)


@pytest.mark.parametrize("axis", ["pp", "sp", "mp"])
def test_meshes_the_model_has_no_path_for_are_refused(axis):
    mesh = build_mesh({"dp": 2, axis: 2}, devices=jax.devices()[:4])
    with pytest.raises(NotImplementedError,
                       match=f"JoyAI-LLM-Flash.*{axis}"):
        build(mesh=mesh)


# ---------------------------------------------------------------------------
# the second prediction depth
# ---------------------------------------------------------------------------
def _grads_of(seeded, **changes):
    params, batches = seeded
    ids, labels = batches[0]
    cfg = dataclasses.replace(config_of(TINY), **changes)
    if not cfg.num_nextn_predict_layers:
        params = {k: v for k, v in params.items() if k != "mtp"}
    loss, _p, opt_state, counters = build(cfg)(*fresh_state(params), ids,
                                               labels)
    return float(loss), first_gradient(opt_state), counters


def test_the_shared_embedding_and_head_take_both_depths_gradients(seeded):
    """``wte`` and ``head_w`` serve both depths: their gradient is the
    main loss's plus lambda times the module's (the step is linear in
    lambda, so lambda = 0.6 adds the module's share once more), and the
    module's share of each is not nought."""
    _, g0, _ = _grads_of(seeded, mtp_loss_weight=0.0)
    _, g1, _ = _grads_of(seeded)
    _, g2, _ = _grads_of(seeded, mtp_loss_weight=2 * LAMBDA)
    for name in ("wte", "head_w"):
        share = np.asarray(g1[name] - g0[name])
        assert np.abs(share).max() > 1e-4, name
        np.testing.assert_allclose(np.asarray(g2[name] - g1[name]), share,
                                   rtol=2e-3, atol=1e-7, err_msg=name)
    # the reference's gradient of the main term alone is the lambda = 0
    # step's, and of the whole loss the lambda = 0.3 step's
    params, (batch, *_) = seeded
    n = batch[0].size
    for lam, got in ((0.0, g0), (LAMBDA, g1)):
        c = dict(TINY, assumed={"mtp_loss_weight": lam})
        want = jax.grad(lambda p: ref.summed_loss(p, *batch, c))(params)
        for name in ("wte", "head_w"):
            np.testing.assert_allclose(got[name], want[name] / n,
                                       rtol=2e-3, atol=2e-7, err_msg=name)


def test_without_weight_the_module_leaves_loss_and_trunk_as_they_were(
        seeded):
    """lambda = 0: the loss and every gradient of the trunk are those of
    the step built without the module, and the module's own leaves take
    no gradient."""
    loss0, g0, counters = _grads_of(seeded, mtp_loss_weight=0.0)
    loss_none, g_none, plain = _grads_of(seeded, num_nextn_predict_layers=0)
    np.testing.assert_allclose(loss0, loss_none, rtol=1e-6)
    np.testing.assert_allclose(counters["loss_main"], loss_none, rtol=1e-6)
    assert "loss_mtp" in counters and "loss_mtp" not in plain
    assert plain["moe_counts"].shape == (2, 4)
    module = g0.pop("mtp")
    assert_trees_close(g0, g_none, rtol=1e-5, atol=1e-8)
    assert not any(np.any(v) for v in leaves(module).values())


def test_a_row_s_last_position_has_no_target_two_tokens_on(seeded):
    """Changing the module's hidden state at a row's last position moves
    neither the loss nor any gradient: the head's row weight there is 0
    (through the interpreted kernel and through the chunked XLA head)."""
    from paddle_tpu.ops.pallas.softmax_xent import softmax_xent_loss
    rs = np.random.RandomState(0)
    x = jnp.asarray(rs.randn(2, 128, 16), jnp.float32)
    w = jnp.asarray(rs.randn(16, 50) * 0.3, jnp.float32)
    labels = jnp.asarray(rs.randint(0, 50, (2, 128)), jnp.int32)
    weight = jnp.broadcast_to(jnp.arange(128) < 127, (2, 128))

    def loss(x, w, fused):
        if fused:
            return softmax_xent_loss(x.reshape(256, 16), w,
                                     labels.reshape(256), True,
                                     weight.reshape(256))
        logits = jnp.einsum("btd,dv->btv", x, w, precision=ref.HIGHEST)
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, labels[..., None], -1)[..., 0]
        return jnp.sum(ce[:, :-1]) / (2 * 127)

    moved = x.at[:, -1].add(3.0)
    for fused in (True, False):
        got, (dx, dw) = jax.value_and_grad(loss, (0, 1))(x, w, fused)
        again, (dx2, dw2) = jax.value_and_grad(loss, (0, 1))(moved, w, fused)
        np.testing.assert_allclose(got, again, rtol=1e-7)
        np.testing.assert_allclose(dw, dw2, atol=1e-9)
        assert not np.any(np.asarray(dx[:, -1]))
        np.testing.assert_allclose(dx[:, :-1], dx2[:, :-1], atol=1e-9)
    np.testing.assert_allclose(loss(x, w, True), loss(x, w, False),
                               rtol=1e-6)
    for a, b in zip(jax.grad(loss, (0, 1))(x, w, True),
                    jax.grad(loss, (0, 1))(x, w, False)):
        np.testing.assert_allclose(a, b, atol=2e-7)
    # and through the whole step: the module's hidden state at the last
    # position reaches nothing
    params, batches = seeded
    ids, labels = batches[0]
    parts = config_of(TINY).spmd_parts(one_device())

    def step_loss(bump):
        out, _c, (depth,) = parts.trunk(params, ids, lambda f: f, labels)
        hidden = depth["hidden"].at[:, -1].add(bump)
        logits = hidden @ params["head_w"]
        ce = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(
            logits, depth["labels"][..., None], -1)[..., 0]
        return jnp.sum(ce * depth["row_weight"]) / jnp.sum(
            depth["row_weight"])

    assert float(jax.grad(step_loss)(0.0)) == 0.0
