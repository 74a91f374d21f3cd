"""LFM2-MoE through ``build_spmd_train_step`` against the plain reference.

Float32 on the CPU at tiny widths with every layer kind (conv or
attention operator x dense or expert FFN); the seeded weights are the
reference's own (``benchmark/references/lfm2_moe.py``), so one tree
serves both sides.  Compared element-wise: the loss, the logits, every
gradient leaf of the first step, the parameters after three AdamW steps.
Then the expert layer alone: nothing dropped under a skewed router, the
eight shares of 64 experts add up to the whole layer, and over a CPU mesh
``ep`` = 4 the layer with its ``all_to_all`` equals the one-device layer.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from benchmark.drivers.lfm2_train import model_config as config_of
from benchmark.references import lfm2_moe as ref
from paddle_tpu.distributed.fleet.meta_parallel.moe import routed_experts
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.models import lfm2_moe as model
from paddle_tpu.models.gpt_spmd import build_spmd_train_step

OPT = {"learning_rate": 3e-4, "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
       "weight_decay": 0.01}
# conv + dense, attention + experts, conv + experts, attention + experts
TINY = {
    "hidden_size": 32, "vocab_size": 64, "num_attention_heads": 4,
    "num_key_value_heads": 2, "intermediate_size": 48,
    "moe_intermediate_size": 16, "num_experts": 4, "conv_L_cache": 3,
    "layer_types": ["conv", "full_attention", "conv", "full_attention"],
    "num_dense_layers": 1, "num_experts_per_tok": 2, "norm_eps": 1e-5,
    "rope_parameters": {"rope_theta": 1e6}, "routed_scaling_factor": 1.0,
    "deployment": {"router_width": 8, "first_expert": 2},
    "assumed": {"optimizer": OPT}}
TRAFFIC = {"batch": 2, "seq_len": 16, "pool": 3, "check_steps": 3}


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


# the reference's loss and gradients of a batch, compiled once
ref_value_and_grad = jax.jit(jax.value_and_grad(
    lambda p, ids, labels: ref.summed_loss(p, ids, labels, TINY)))


@pytest.fixture(scope="module")
def seeded():
    params = ref.init_params(TINY, 5)
    batches = [(jnp.asarray(i), jnp.asarray(l))
               for i, l in ref.make_batches(TINY, TRAFFIC, 5)]
    return params, batches


def test_every_layer_kind_is_present():
    cfg = config_of(TINY)
    kinds = {(t, l < cfg.num_dense_layers)
             for l, t in enumerate(cfg.layer_types)}
    assert kinds >= {("conv", True), ("conv", False),
                     ("full_attention", False)}
    assert cfg.held == 4 and cfg.num_experts == 8 and cfg.first_expert == 2


def test_logits_match_the_reference(seeded):
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
    first = TINY["deployment"]["first_expert"]
    for l, idx in enumerate(chosen):
        want_counts = [(np.asarray(idx) == first + e).sum()
                       for e in range(TINY["num_experts"])]
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
    for layer in grads["layers"]:
        if "router_bias" in layer:
            assert not np.any(np.asarray(layer["router_bias"]))
            assert np.any(np.asarray(layer["router_w"]))


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
    # Adam's first steps move every weight by about lr whatever the
    # gradient's size, so a gradient's rounding shows where it is small
    assert_trees_close(p, want, rtol=1e-4, atol=2e-5)
    moved = 0
    for got, was in zip(p["layers"], params["layers"]):
        if "router_bias" in got:
            np.testing.assert_array_equal(got["router_bias"],
                                          was["router_bias"])
            assert np.any(np.asarray(got["router_w"] != was["router_w"]))
            moved += 1
    assert moved == 3


# ---------------------------------------------------------------------------
# the expert layer by itself
# ---------------------------------------------------------------------------
def _expert_layer(seed, D=16, F=8, E=8, held=8, N=(2, 24)):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    normal = lambda k, *s, std=0.3: jax.random.normal(   # noqa: E731
        k, s, jnp.float32) * std
    return {"x": normal(ks[0], *N, D, std=1.0),
            "router_w": normal(ks[1], D, E),
            "router_bias": normal(ks[2], E, std=0.01),
            "w1": normal(ks[3], held, D, F), "w3": normal(ks[4], held, D, F),
            "w2": normal(ks[5], held, F, D)}


def _reference_experts(p, first, k, width):
    """The reference's expert FFN on the layer's input as it stands."""
    c = {"num_experts_per_tok": k, "routed_scaling_factor": 1.0,
         "num_experts": p["w1"].shape[0],
         "deployment": {"router_width": width, "first_expert": first}}
    mm = lambda spec, a, b: jnp.einsum(   # noqa: E731
        spec, a, b, precision=ref.HIGHEST)
    return ref._experts_ffn(p, p["x"], c, mm)


def _program_experts(p, first, k, **kw):
    return routed_experts(p["x"], p["router_w"], p["router_bias"], p["w1"],
                          p["w3"], p["w2"], top_k=k, first_expert=first,
                          **kw)


def test_no_assignment_is_dropped_under_a_skewed_router():
    """One expert takes nearly every token: a capacity layer would drop
    most of them; here every assignment is served and counted."""
    p = _expert_layer(0)
    p["router_bias"] = p["router_bias"].at[3].set(10.0)
    y, counts, overflow = _program_experts(p, 0, 2)
    n = p["x"].shape[0] * p["x"].shape[1]
    assert int(counts[3]) == n and int(counts.sum()) == 2 * n
    assert int(overflow) == 0
    want, _ = _reference_experts(p, 0, 2, 8)
    np.testing.assert_allclose(y, want, rtol=1e-4, atol=1e-6)
    # and its gradient, through the sort and the segment offsets
    loss = lambda f: lambda p: jnp.sum(jnp.sin(f(p)))   # noqa: E731
    got = jax.jit(jax.grad(loss(lambda p: _program_experts(p, 0, 2)[0])))(p)
    ref_g = jax.jit(jax.grad(loss(
        lambda p: _reference_experts(p, 0, 2, 8)[0])))(p)
    assert_trees_close(got, ref_g, rtol=2e-3, atol=1e-6)


def test_a_buffer_that_is_too_small_counts_what_it_leaves_out():
    p = _expert_layer(1)
    p["router_bias"] = p["router_bias"].at[5].set(10.0)
    n = p["x"].shape[0] * p["x"].shape[1]
    _, counts, overflow = _program_experts(p, 0, 2, rows=n)
    assert int(counts.sum()) == 2 * n and int(overflow) == n


def test_the_eight_shares_add_up_to_the_whole_layer():
    """The layer told a different eighth of 64 experts each time: the
    parts sum to the uncut reference's whole layer (there is no shared
    expert to count once), and every assignment is somebody's."""
    p = _expert_layer(2, E=64, held=64)
    whole, _ = _reference_experts(p, 0, 4, 64)
    total, served = jnp.zeros_like(whole), 0
    for first in range(0, 64, 8):
        share = dict(p, **{w: p[w][first:first + 8]
                           for w in ("w1", "w3", "w2")})
        y, counts, overflow = _program_experts(share, first, 4)
        part, _ = _reference_experts(share, first, 4, 64)
        np.testing.assert_allclose(y, part, rtol=1e-4, atol=1e-6)
        total, served = total + y, served + int(counts.sum())
        assert int(overflow) == 0
    np.testing.assert_allclose(total, whole, rtol=1e-4, atol=1e-6)
    assert served == 4 * p["x"].shape[0] * p["x"].shape[1]


@pytest.mark.parametrize("dims", [{"ep": 4}, {"dp": 2, "ep": 4}],
                         ids=["ep4", "dp2-ep4"])
def test_experts_over_ep_equal_the_one_device_layer(dims):
    n = int(np.prod(list(dims.values())))
    mesh = build_mesh(dims, devices=jax.devices()[:n])
    p = _expert_layer(3, N=(8, 6))
    p["router_bias"] = p["router_bias"].at[6].set(0.3)      # uneven
    axes = tuple(dims)

    def over_ep(p):
        return _program_experts(p, 0, 2, mesh=mesh, token_axes=axes,
                                ep_axis="ep")

    want_y, want_counts, _ = _program_experts(p, 0, 2)
    y, counts, overflow = jax.jit(over_ep)(p)
    np.testing.assert_allclose(y, want_y, rtol=1e-5, atol=1e-6)
    assert counts.tolist() == want_counts.tolist()
    assert int(overflow) == 0
    loss = lambda f: lambda p: jnp.sum(jnp.sin(f(p)[0]))   # noqa: E731
    assert_trees_close(
        jax.jit(jax.grad(loss(over_ep)))(p),
        jax.grad(loss(lambda p: _program_experts(p, 0, 2)))(p),
        rtol=2e-3, atol=1e-6)


def test_the_step_over_dp_and_ep_matches_one_device(seeded):
    params, batches = seeded
    c = dict(TINY, deployment={"router_width": 8, "first_expert": 0})
    ids = jnp.concatenate([b[0] for b in batches[:2]] * 2)     # batch 8
    labels = jnp.concatenate([b[1] for b in batches[:2]] * 2)
    want = build(c)(*fresh_state(params), ids, labels)
    mesh = build_mesh({"dp": 2, "ep": 4}, devices=jax.devices()[:8])
    got = build(c, mesh)(*fresh_state(params), ids, labels)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    assert_trees_close(got[2]["m"], want[2]["m"], rtol=2e-3, atol=1e-7)
    assert got[3]["moe_counts"].tolist() == want[3]["moe_counts"].tolist()


@pytest.mark.parametrize("axis", ["pp", "sp", "mp"])
def test_meshes_the_model_has_no_path_for_are_refused(axis):
    mesh = build_mesh({"dp": 2, axis: 2}, devices=jax.devices()[:4])
    with pytest.raises(NotImplementedError, match=axis):
        build(TINY, mesh)


# ---------------------------------------------------------------------------
# grouped-query attention: q/k norm and RoPE against plain math
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("T,force", [(24, False), (128, True)],
                         ids=["fallback", "kernel-interpreted"])
def test_gqa_with_qk_norm_and_rope(T, force, monkeypatch):
    """T = 24 is not a multiple of 128 and takes XLA math; T = 128 under
    ``PADDLE_PALLAS_FORCE`` takes the small-T kernels, interpreted."""
    from paddle_tpu.ops import pallas
    if force:
        monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")
    c = dict(TINY, layer_types=["full_attention"], num_dense_layers=1)
    cfg = config_of(c)
    ks = jax.random.split(jax.random.PRNGKey(7), 3)
    p = ref.init_params(c, 11)["layers"][0]
    p = dict(p, q_norm=1 + 0.1 * jax.random.normal(ks[0], (8,)),
             k_norm=1 + 0.1 * jax.random.normal(ks[1], (8,)))
    x = jax.random.normal(ks[2], (2, T, 32), jnp.float32)
    mm = lambda spec, a, b: jnp.einsum(   # noqa: E731
        spec, a, b, precision=ref.HIGHEST)

    def want(p, x):
        z = ref._rms(x, p["op_norm"], c["norm_eps"])
        return x + ref._attention_op(p, z, c, mm)

    def got(p, x):
        return model._gqa(p, x, cfg, one_device(), ())

    before = dict(pallas.selections())
    np.testing.assert_allclose(jax.jit(got)(p, x), jax.jit(want)(p, x),
                               rtol=1e-4, atol=1e-5)
    took = {k for k, v in pallas.selections().items()
            if v != before.get(k, 0)}
    assert took == ({"flash_attention.small.interpret"} if force
                    else {"flash_attention.xla"})
    loss = lambda f: lambda p, x: jnp.sum(jnp.sin(f(p, x)))   # noqa: E731
    assert_trees_close(jax.jit(jax.grad(loss(got), (0, 1)))(p, x),
                       jax.jit(jax.grad(loss(want), (0, 1)))(p, x),
                       rtol=2e-3, atol=2e-5)
