"""The names the GPT train step carries into the device trace.

``build_spmd_train_step`` marks its parts with ``jax.named_scope``
(embed, unstack, attn_qkv, attn_out, ffn, final_ln, loss_head,
optimizer); the benchmark's ``scope_time`` metrics read device time by
those names and by the phase the path shows (``jvp(`` forward,
``transpose(`` backward, ``rematted_computation`` recompute).  Two things
are guarded here, at no chip time:

- on the CPU, that every scope reaches the compiled program in every
  phase, at each remat policy and on the pp / sp paths;
- for a described v5e, that no scope (and no ``name=``) renamed a Mosaic
  call: the TPU compiler names a ``tpu_custom_call`` after the last
  name-stack component in front of ``pallas_call``, and the benchmark's
  roofline metrics find the kernels by exactly those names
  (``benchmark/layer_metrics/*_roofline.json``).  A kernel call that
  slips inside a scope would fail a ``--trace 1`` run on the chip.
"""
import json
import os
import re
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from benchmark.harness.scopes import classify
from hlo_text import HANDOVER_SHAPES, handover_copies
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.models import GPTConfig
from paddle_tpu.models.gpt_spmd import (build_spmd_train_step,
                                        gpt_param_shardings,
                                        init_gpt_params)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TINY = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2, num_heads=2,
                 max_seq_len=16, ffn_mult=2)
BLOCK = ("attn_qkv", "attn_out", "ffn")
DIFFERENTIATED = ("embed", "unstack", *BLOCK, "final_ln", "loss_head")
POLICIES = ("none", "ctx", "ctx_ffn", "dots", "full")


def _lower_step(step, shapes, shardings, mesh, batch, seq_len, baxes=()):
    """A step lowered from shapes alone, so that the same helper serves
    the CPU mesh and a described TPU."""
    params = jax.tree.map(
        lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns),
        shapes, shardings)
    opt = {"m": params, "v": params,
           "step": jax.ShapeDtypeStruct(
               (), jnp.int32, sharding=NamedSharding(mesh, P()))}
    ids = jax.ShapeDtypeStruct(
        (batch, seq_len), jnp.int32,
        sharding=NamedSharding(mesh, P(baxes or None)))
    return step.lower(params, opt, ids, ids)


def _lower(cfg, mesh, batch, compute_dtype=jnp.bfloat16, **kw):
    step, _ = build_spmd_train_step(cfg, mesh, compute_dtype=compute_dtype,
                                    **kw)
    shapes = jax.eval_shape(
        lambda: init_gpt_params(cfg, jax.random.PRNGKey(0)))
    baxes = tuple(a for a in ("dp", "sharding")
                  if mesh.shape.get(a, 1) > 1)
    return _lower_step(step, shapes, gpt_param_shardings(mesh, cfg), mesh,
                       batch, cfg.max_seq_len, baxes)


def _op_names(hlo_text):
    return set(re.findall(r'op_name="([^"]*)"', hlo_text))


def _under(name, scopes):
    """The scope among ``scopes`` that the op_name path sits under."""
    m = re.search(r"[/(](%s)[/)]" % "|".join(scopes), name)
    return m and m.group(1)


_compiled = {}


def _tiny_step_text(policy):
    if policy not in _compiled:
        _compiled[policy] = _lower(
            TINY, build_mesh({"dp": 1}), 4,
            remat_policy=policy).compile().as_text()
    return _compiled[policy]


def test_the_step_has_a_name_of_its_own():
    """The compile cache's key ignores scopes (they are locations, and
    the key strips debug info) but hashes symbols: the jitted function's
    name is what tells this step from any other one called ``step``."""
    assert "HloModule jit_gpt_spmd_train_step" in _tiny_step_text("ctx")


@pytest.mark.parametrize("policy", POLICIES)
def test_every_scope_forward_and_backward(policy):
    names = _op_names(_tiny_step_text(policy))
    for scope in DIFFERENTIATED:
        assert any(f"/jvp({scope})/" in n for n in names), scope
        assert any("transpose(" in n and _under(n, [scope])
                   for n in names), scope
    # the optimizer is not differentiated: its scope stands bare
    assert any("/optimizer/" in n and "transpose(" not in n for n in names)


@pytest.mark.parametrize("policy", [p for p in POLICIES if p != "none"])
def test_block_scopes_under_recompute(policy):
    names = _op_names(_tiny_step_text(policy))
    remat = {_under(n, BLOCK) for n in names if "rematted_computation" in n}
    # what is saved is not recomputed: ctx_ffn keeps the FFN's up
    # projection, dots every matmul, but LayerNorm 1 is always redone
    assert "attn_qkv" in remat
    if policy in ("ctx", "full"):
        assert remat >= set(BLOCK)


def test_no_block_recompute_without_remat():
    # (the chunked loss head checkpoints its rows at every policy)
    assert not any("rematted_computation" in n and _under(n, BLOCK)
                   for n in _op_names(_tiny_step_text("none")))


@pytest.mark.parametrize("policy", POLICIES)
def test_block_matmuls_sit_under_a_block_scope(policy):
    """Every dot_general but attention's own (XLA math on the CPU, under
    no scope because the kernel call is a sibling of the scopes) and the
    loss head's sits under attn_qkv, attn_out or ffn."""
    dots = [n for n in _op_names(_tiny_step_text(policy))
            if n.endswith("dot_general")]
    attention = [n for n in dots if "bqd,bkd->bqk" in n or "bqk,bkd->bqd" in n]
    assert attention and not any(
        _under(n, DIFFERENTIATED + ("optimizer",)) for n in attention)
    block = [n for n in dots
             if n not in attention and not _under(n, ["loss_head"])]
    assert len(block) >= 4
    assert all(_under(n, BLOCK) for n in block), \
        [n for n in block if not _under(n, BLOCK)]


@pytest.mark.parametrize("dims,kw", [
    ({"dp": 2, "pp": 2, "mp": 2}, dict(num_microbatches=2)),
    ({"dp": 2, "pp": 2, "mp": 2}, dict(num_microbatches=2,
                                       schedule_mode="1F1B")),
    ({"dp": 2, "sp": 2}, {}),
], ids=["pp-F-then-B", "pp-1F1B", "sp"])
def test_mesh_paths_carry_the_scopes(dims, kw):
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4,
                    num_heads=2, max_seq_len=16, ffn_mult=2)
    # float32: XLA's CPU compiler aborts on these meshes in bfloat16
    names = _op_names(_lower(cfg, build_mesh(dims), 8, jnp.float32,
                             **kw).compile().as_text())
    for scope in ("embed", *BLOCK, "final_ln", "loss_head", "optimizer"):
        assert any(_under(n, [scope]) for n in names), scope


# ---------------------------------------------------------------------------
# for a described v5e: the Mosaic calls keep the names the benchmark finds
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"no device-less TPU topology here: {e!r}")
    return list(topo.devices)


def _real_width_step_hlo(v5e, batch, seq_len):
    """Two layers at gpt2-medium's widths (V=50257, ``ctx`` remat)
    compiled for one v5e chip, Mosaic steered on here and not through an
    option of the program."""
    from paddle_tpu.ops import pallas
    mp = pytest.MonkeyPatch()
    for mod in (pallas,
                sys.modules["paddle_tpu.ops.pallas.flash_attention"]):
        mp.setattr(mod, "on_tpu", lambda: True)
    try:
        cfg = GPTConfig(vocab_size=50257, hidden_size=1024, num_layers=2,
                        num_heads=16, max_seq_len=seq_len, ffn_mult=4)
        mesh = Mesh(np.asarray(v5e[:1]), ("dp",))
        return cfg, _lower(cfg, mesh, batch,
                           remat_policy="ctx").compile().as_text()
    finally:
        mp.undo()


@pytest.fixture(scope="module")
def real_width_step_hlo(v5e):
    """The benchmark's cell, B=32 x T=1024: the mid-regime kernels."""
    return _real_width_step_hlo(v5e, 32, 1024)


@pytest.fixture(scope="module")
def small_t_step_hlo(v5e):
    """B=128 x T=256: the small-regime kernels, which no cell runs."""
    return _real_width_step_hlo(v5e, 128, 256)


def _patterns(metric):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        return [re.compile(p) for p in json.load(f)["patterns"]]


def _instructions(hlo_text):
    """Instruction lines as the device trace names its events: the
    instruction's text from its ``%name`` on."""
    return [re.sub(r"^\s*(ROOT )?", "", line)
            for line in hlo_text.splitlines() if " = " in line]


def _mosaic_calls(hlo_text):
    return [i for i in _instructions(hlo_text)
            if 'custom_call_target="tpu_custom_call"' in i]


def test_every_mosaic_call_is_one_the_benchmark_finds(real_width_step_hlo):
    cfg, hlo = real_width_step_hlo
    mosaic = _mosaic_calls(hlo)
    assert len(mosaic) == 2 * cfg.num_layers + 1, \
        [m.split(" = ")[0] for m in mosaic]
    known = _patterns("attn_roofline") + _patterns("xent_head_roofline")
    strangers = [m[:120] for m in mosaic
                 if not any(r.search(m) for r in known)]
    assert not strangers, (
        "Mosaic calls no roofline pattern finds — did a scope or a "
        f"name= get around a pallas_call? {strangers}")


@pytest.mark.parametrize("metric,pattern,per_layer", [
    ("attn_roofline", 0, True), ("attn_roofline", 1, True),
    ("xent_head_roofline", 0, False)],
    ids=["flash-forward", "flash-backward", "fused-head-forward"])
def test_each_kernel_pattern_finds_its_calls(
        real_width_step_hlo, metric, pattern, per_layer):
    cfg, hlo = real_width_step_hlo
    rx = _patterns(metric)[pattern]
    hit = [m for m in _mosaic_calls(hlo) if rx.search(m)]
    assert len(hit) == (cfg.num_layers if per_layer else 1)


def test_loss_head_backward_loop_keeps_its_name(real_width_step_hlo):
    """``xent_head_roofline`` finds the head's backward as XLA's while
    loop over row chunks whose carry holds the f32 (D, V) gradient."""
    _cfg, hlo = real_width_step_hlo
    loop = _patterns("xent_head_roofline")[1]
    assert any(loop.search(i) for i in _instructions(hlo))


# ---------------------------------------------------------------------------
# for a described v5e: attention pays nothing for layout
# ---------------------------------------------------------------------------
def _qkv_sized_layout_work(cfg, hlo, batch):
    """Instructions that move a q+k+v-sized array without computing
    anything: a ``copy``, a ``dynamic-update-slice`` (alone or as a
    fusion), a ``concatenate`` or a ``pad`` whose result holds the three
    projections of a whole batch — packed 3-D or 4-D, or stacked — in
    any layout."""
    T, D = cfg.max_seq_len, cfg.hidden_size
    sized = re.compile(
        r"^(%%[\w.\-]*) = bf16\[(?:%d,%d,%d|%d,%d,3,%d|3,%d,%d,%d)\]\S* "
        r"([\w\-]+)\(" % (batch, T, 3 * D, batch, T, D, batch, T, D))
    moves = re.compile(r"copy|dynamic-update-slice|concatenate|pad")
    found = []
    for ins in _instructions(hlo):
        m = sized.match(ins)
        # a fusion is named after what it holds
        if m and moves.search(m.group(1) + " " + m.group(2)):
            found.append(ins[:100])
    return found


@pytest.mark.parametrize("fixture,batch", [
    ("real_width_step_hlo", 32), ("small_t_step_hlo", 128)],
    ids=["t1024-mid", "t256-small"])
def test_no_qkv_sized_layout_work_around_attention(request, fixture, batch):
    """q, k, v and their gradients cross the flash-attention kernels in
    the layout the projection matmuls use: the program holds no relayout
    copy and no packing update of q+k+v size (a packed 4-D projection
    cost 3 copies + 3 updates a layer: 57 of 779 ms a step on the chip,
    PERF.md PR 29), and still 2 Mosaic calls a layer + the loss head."""
    cfg, hlo = request.getfixturevalue(fixture)
    assert not _qkv_sized_layout_work(cfg, hlo, batch)
    assert len(_mosaic_calls(hlo)) == 2 * cfg.num_layers + 1


# ---------------------------------------------------------------------------
# the LFM2-MoE step: its own scopes, its own name, its own kernels
# ---------------------------------------------------------------------------
LFM2_SCOPES = ("embed", "short_conv", "gqa_qkv", "gqa_out", "dense_ffn",
               "moe_route", "moe_dispatch", "moe_experts", "moe_combine",
               "final_norm")


def _lower_lfm2(cfg, mesh, batch, seq_len, **kw):
    step, _ = build_spmd_train_step(cfg, mesh, compute_dtype=jnp.bfloat16,
                                    **kw)
    parts = cfg.spmd_parts(mesh)
    return _lower_step(
        step, jax.eval_shape(parts.init, jax.random.PRNGKey(0)),
        parts.shardings, mesh, batch, seq_len)


@pytest.fixture(scope="module")
def tiny_lfm2_step_text():
    from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig
    cfg = Lfm2MoeConfig(
        vocab_size=128, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_dense_layers=1, num_experts=8,
        layer_types=("conv", "full_attention", "conv"),
        num_experts_per_tok=2, num_experts_held=4, num_attention_heads=4,
        num_key_value_heads=2)
    return _lower_lfm2(cfg, build_mesh({"dp": 1}), 2, 16,
                       remat_policy="ctx").compile().as_text()


def test_the_lfm2_step_has_its_own_name_and_scopes(tiny_lfm2_step_text):
    assert "HloModule jit_lfm2_moe_spmd_train_step" in tiny_lfm2_step_text
    names = _op_names(tiny_lfm2_step_text)
    for scope in LFM2_SCOPES:
        assert any(f"jvp({scope})" in n for n in names), scope
        assert any("transpose(" in n and _under(n, [scope])
                   for n in names), scope
    assert any("/optimizer/" in n and "transpose(" not in n for n in names)
    # GPT's block scopes are GPT's
    assert not any(_under(n, BLOCK + ("unstack", "final_ln")) for n in names)


def test_lfm2_matmuls_sit_under_a_scope(tiny_lfm2_step_text):
    """Every dot_general but attention's own (a sibling of the scopes,
    like GPT's) and the loss head's sits under one of the model's scopes;
    the grouped expert matmuls under ``moe_experts``."""
    names = _op_names(tiny_lfm2_step_text)
    dots = [n for n in names if n.endswith("dot_general")]
    attention = [n for n in dots if "bqd,bkd->bqk" in n or "bqk,bkd->bqd" in n]
    assert attention and not any(_under(n, LFM2_SCOPES) for n in attention)
    rest = [n for n in dots
            if n not in attention and not _under(n, ["loss_head"])]
    assert rest and all(_under(n, LFM2_SCOPES) for n in rest), \
        [n for n in rest if not _under(n, LFM2_SCOPES)]
    # (off the TPU ``lax.ragged_dot`` lowers to masked dot_generals)
    assert any(_under(n, ["moe_experts"]) for n in rest)


@pytest.fixture(scope="module")
def lfm2_real_width_hlo(v5e):
    """An attention + dense layer and a conv + experts layer at the
    published widths and the cell's B=4 x T=8192, for one v5e chip."""
    from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig
    from paddle_tpu.ops import pallas
    mp = pytest.MonkeyPatch()
    for mod in (pallas,
                sys.modules["paddle_tpu.ops.pallas.flash_attention"]):
        mp.setattr(mod, "on_tpu", lambda: True)
    try:
        cfg = Lfm2MoeConfig(vocab_size=8192, num_dense_layers=1,
                            layer_types=("full_attention", "conv"),
                            num_experts_held=8, moe_rows_factor=2.0)
        mesh = Mesh(np.asarray(v5e[:1]), ("dp",))
        return _lower_lfm2(cfg, mesh, 4, 8192,
                           remat_policy="ctx").compile().as_text()
    finally:
        mp.undo()


def test_every_lfm2_mosaic_call_is_one_the_benchmark_finds(
        lfm2_real_width_hlo):
    """The stream regime's resident flash pair (one forward, one fused
    backward), the compiler's grouped expert matmuls and the fused loss
    head: each Mosaic call matches a pattern of exactly one of the
    cell's metric files."""
    mosaic = _mosaic_calls(lfm2_real_width_hlo)
    groups = {m: _patterns(m) for m in (
        "stream_attn_roofline", "moe_experts_roofline",
        "lfm2_loss_head_events")}
    hits = {m: [c for c in mosaic if any(r.search(c) for r in rx)]
            for m, rx in groups.items()}
    # the patterns are any-of at run time (harness/trace.py `matching`):
    # the forward is the (out bf16, lse f32 (BH, T, 1)) call, the
    # backward the call whose first result is dq
    attention = hits["stream_attn_roofline"]
    assert len(attention) == 2, [c[:100] for c in attention]
    assert sum(c.startswith("%jvp__") for c in attention) == 1
    assert sum(c.startswith("%checkpoint") for c in attention) == 1
    # ``ctx`` keeps the forward's out and lse: nothing of flash
    # attention is run again for the backward
    assert not any(c.startswith("%rematted_computation")
                   for c in attention)
    flash_forward = groups["stream_attn_roofline"][0].pattern.replace(
        "^%jvp__", "^%")
    assert sum(bool(re.search(flash_forward, c)) for c in mosaic) == 1
    # three grouped matmuls forward, three recomputed, six backward, and
    # the compiler's own calls that lay out the groups for them
    experts = hits["moe_experts_roofline"]
    assert sum(c.startswith("%ragged-dot-metadata") for c in experts) >= 1
    assert sum(not c.startswith("%ragged-dot-metadata")
               for c in experts) == 12
    assert len(hits["lfm2_loss_head_events"]) == 1
    assert sum(map(len, hits.values())) == len(mosaic), \
        [c[:100] for c in mosaic
         if not any(c in h for h in hits.values())]
    # and GPT's attention patterns do not claim the loss head or experts
    gpt_fwd = _patterns("attn_roofline")[0]
    assert not any(gpt_fwd.search(c) for c in mosaic)


def test_the_gpt_step_holds_the_mosaic_calls_it_held(real_width_step_hlo):
    """The ``ctx`` policies list the stream regime's residual names,
    which no GPT-length kernel carries: the compiled GPT step keeps its
    2 x layers + 1 Mosaic calls under the names it had (flash forward
    ``jvp__``, fused backward ``checkpoint``, the loss head), none run
    again."""
    cfg, hlo = real_width_step_hlo
    names = sorted(re.sub(r"[.\d]*$", "", c.split(" = ")[0])
                   for c in _mosaic_calls(hlo))
    assert names == sorted(["%jvp__"] * (cfg.num_layers + 1)
                           + ["%checkpoint"] * cfg.num_layers), names


# ---------------------------------------------------------------------------
# the Qwen3-Next step: its own scopes, its own name, its own kernels
# ---------------------------------------------------------------------------
QWEN3_NEXT_SCOPES = (
    "embed", "gdn_in", "gdn_conv", "gdn_scan", "gdn_out", "gattn_qkv",
    "gattn_out", "shared_expert", "moe_route", "moe_dispatch",
    "moe_experts", "moe_combine", "final_norm")


@pytest.fixture(scope="module")
def tiny_qwen3_next_step_text():
    from paddle_tpu.models import Qwen3NextConfig
    cfg = Qwen3NextConfig(
        vocab_size=128, hidden_size=32, num_hidden_layers=2,
        full_attention_interval=2, num_attention_heads=4,
        num_key_value_heads=2, head_dim=16, linear_num_key_heads=2,
        linear_num_value_heads=4, linear_key_head_dim=8,
        linear_value_head_dim=8, num_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=16, shared_expert_intermediate_size=16,
        num_experts_held=4, gdn_chunk=8)
    return _lower_lfm2(cfg, build_mesh({"dp": 1}), 2, 16,
                       remat_policy="ctx").compile().as_text()


def test_the_qwen3_next_step_has_its_own_name(tiny_qwen3_next_step_text):
    assert "HloModule jit_qwen3_next_spmd_train_step" \
        in tiny_qwen3_next_step_text
    names = _op_names(tiny_qwen3_next_step_text)
    assert any("/optimizer/" in n and "transpose(" not in n for n in names)
    # GPT's and LFM2's scopes are theirs
    assert not any(_under(n, BLOCK + ("unstack", "final_ln", "short_conv",
                                      "gqa_qkv", "gqa_out", "dense_ffn"))
                   for n in names)


@pytest.mark.parametrize("scope", QWEN3_NEXT_SCOPES)
def test_a_qwen3_next_scope_forward_and_backward(
        tiny_qwen3_next_step_text, scope):
    names = _op_names(tiny_qwen3_next_step_text)
    assert any(f"jvp({scope})" in n for n in names), scope
    assert any("transpose(" in n and _under(n, [scope]) for n in names), \
        scope


def test_qwen3_next_matmuls_sit_under_a_scope(tiny_qwen3_next_step_text):
    """Every dot_general but attention's own (a sibling of the scopes,
    like the other models') and the loss head's sits under one of the
    model's scopes; the chunked rule's — the products inside a chunk and
    the state's in the scan over chunks — under ``gdn_scan``."""
    names = _op_names(tiny_qwen3_next_step_text)
    dots = [n for n in names if n.endswith("dot_general")]
    attention = [n for n in dots if "bqd,bkd->bqk" in n or "bqk,bkd->bqd" in n]
    assert attention and not any(_under(n, QWEN3_NEXT_SCOPES)
                                 for n in attention)
    rest = [n for n in dots
            if n not in attention and not _under(n, ["loss_head"])]
    assert rest and all(_under(n, QWEN3_NEXT_SCOPES) for n in rest), \
        [n for n in rest if not _under(n, QWEN3_NEXT_SCOPES)]
    scan = [n for n in rest if _under(n, ["gdn_scan"])]
    # the rows' loop holds the products inside a chunk, and the loop
    # over chunks inside it the state's
    assert {min(n.count("while/body"), 2) for n in scan} == {1, 2}
    assert any(_under(n, ["moe_experts"]) for n in rest)
    assert any(_under(n, ["shared_expert"]) for n in rest)
    # the triangular inverse of a chunk is the rule's too
    assert any(_under(n, ["gdn_scan"]) and "triangular_solve" in n
               for n in names)


@pytest.fixture(scope="module")
def qwen3_next_real_width_hlo(v5e):
    """A delta-rule layer and a gated-attention layer at the published
    widths, the cell's share (16 of 512 experts, V = 18 992) and the
    cell's B=4 x T=8192, for one v5e chip.  A v5e reports 128 MiB of
    VMEM, a described one nothing: the capacity is steered here."""
    from paddle_tpu.models import Qwen3NextConfig
    from paddle_tpu.ops import pallas
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    mp = pytest.MonkeyPatch()
    for mod in (pallas, fa):
        mp.setattr(mod, "on_tpu", lambda: True)
    mp.setattr(fa, "_vmem_capacity", lambda: 128 << 20)
    try:
        cfg = Qwen3NextConfig(vocab_size=18992, num_hidden_layers=2,
                              full_attention_interval=2,
                              num_experts_held=16, moe_rows_factor=2.0)
        mesh = Mesh(np.asarray(v5e[:1]), ("dp",))
        return _lower_lfm2(cfg, mesh, 4, 8192,
                           remat_policy="ctx").compile().as_text()
    finally:
        mp.undo()


def _scope_phases(calls):
    """(name, (scope, phase)) of Mosaic calls, as the trace's reader
    classifies their ``op_name`` paths."""
    return sorted(
        (c.split(".")[0],
         classify(re.search(r'op_name="([^"]*)"', c).group(1),
                  set(QWEN3_NEXT_SCOPES)))
        for c in calls)


def test_every_qwen3_next_mosaic_call_is_one_the_benchmark_finds(
        qwen3_next_real_width_hlo):
    """The resident flash pair at head size 256 (one forward, one fused
    backward, nothing run again), the compiler's grouped expert matmuls
    and the fused loss head at V = 18 992: each matches a pattern of
    exactly one of the cell's metric files.  The rule's own kernels a
    delta-rule layer — the prep and the loop's forward; in the backward
    the prep again (it writes the inverse this time), the state pass, the
    loop's reverse pass and the prep's; the forward is not run again, its
    output is saved by name — match none of those patterns:
    ``gdn_scan_roofline`` finds them by the scope on their path, which is
    what the device trace's ``tf_op`` holds.  The convolution's kernel
    pair sits under ``gdn_conv`` the same way: ``%gdn_conv_fwd`` in the
    forward and again in the recompute (the block is run again under
    ``ctx``), ``%gdn_conv_bwd`` in the backward.  The compile is also the
    proof that the kernels fit VMEM at the cell's shapes."""
    mosaic = _mosaic_calls(qwen3_next_real_width_hlo)
    groups = {m: _patterns(m) for m in (
        "gattn_roofline", "qwen3next_moe_experts_roofline",
        "qwen3next_loss_head_events")}
    rule = [c for c in mosaic if c.startswith("%delta_rule_")]
    phases = _scope_phases(rule)
    assert phases == [
        ("%delta_rule_bwd", ("gdn_scan", "backward")),
        ("%delta_rule_fwd", ("gdn_scan", "forward")),
        ("%delta_rule_prep", ("gdn_scan", "backward")),
        ("%delta_rule_prep", ("gdn_scan", "forward")),
        ("%delta_rule_prep_bwd", ("gdn_scan", "backward")),
        ("%delta_rule_states", ("gdn_scan", "backward"))], phases
    conv = [c for c in mosaic if c.startswith("%gdn_conv_")]
    assert _scope_phases(conv) == [
        ("%gdn_conv_bwd", ("gdn_conv", "backward")),
        ("%gdn_conv_fwd", ("gdn_conv", "forward")),
        ("%gdn_conv_fwd", ("gdn_conv", "recompute"))], _scope_phases(conv)
    for c in rule + conv:
        assert not any(r.search(c) for rx in groups.values() for r in rx)
    # q, k and v leave the forward kernel as three arrays in the layout
    # the rule's prep reads: (B, T, heads x 128), tokens on sublanes
    for c in conv:
        if c.startswith("%gdn_conv_fwd"):
            results = c.split(" custom-call(")[0]
            assert results.count("bf16[4,8192,2048]{2,1,0") == 2 \
                and results.count("bf16[4,8192,4096]{2,1,0") == 1, results
    # the loop over chunks is the kernels' grid: what is left under the
    # scope loops over the batch rows at most (the tiny CPU step above
    # nests the chunks' loop in the rows': depth 2), and no operation of
    # the scan's step — a product with the state — is XLA's any more;
    # nor is the prep: no inverse by ``solve_triangular``, no ``K K^T``
    # or ``Q K^T`` over key heads repeated in HBM
    under = [n for n in _op_names(qwen3_next_real_width_hlo)
             if _under(n, ["gdn_scan"])]
    assert under and max(n.count("while/body") for n in under) <= 1
    assert not any("hcd,hde->hce" in n or "nhcd,nhsd->nhcs" in n
                   or "triangular_solve" in n for n in under)
    assert not any("triangular" in i.lower() or "InvertDiagBlocks" in i
                   for i in _instructions(qwen3_next_real_width_hlo))
    mosaic = [c for c in mosaic if c not in rule + conv]
    hits = {m: [c for c in mosaic if any(r.search(c) for r in rx)]
            for m, rx in groups.items()}
    attention = hits["gattn_roofline"]
    assert len(attention) == 2, [c[:100] for c in attention]
    assert sum(c.startswith("%jvp__") for c in attention) == 1
    assert sum(c.startswith("%checkpoint") for c in attention) == 1
    assert "bf16[64,8192,256]" in attention[0]
    # two expert layers: three grouped matmuls forward, three recomputed
    # and six backward each, and the compiler's own layout calls
    experts = hits["qwen3next_moe_experts_roofline"]
    assert sum(not c.startswith("%ragged-dot-metadata")
               for c in experts) == 24
    assert len(hits["qwen3next_loss_head_events"]) == 1
    assert sum(map(len, hits.values())) == len(mosaic), \
        [c[:100] for c in mosaic
         if not any(c in h for h in hits.values())]
    loop = groups["qwen3next_loss_head_events"][1]
    assert any(loop.search(i)
               for i in _instructions(qwen3_next_real_width_hlo))
    # LFM2's generic stream pattern would find these two calls as well:
    # each cell lists its own metric, so neither reads the other's step
    assert not any(_patterns("attn_roofline")[0].search(c) for c in mosaic)


# ---------------------------------------------------------------------------
# the JoyAI-LLM-Flash step: its own scopes, its own name, its own kernels
# ---------------------------------------------------------------------------
JOYAI_SCOPES = (
    "embed", "mla_q", "mla_kv", "mla_out", "dense_ffn", "shared_expert",
    "moe_route", "moe_dispatch", "moe_experts", "moe_combine", "mtp_in",
    "mtp", "final_norm")


@pytest.fixture(scope="module")
def tiny_joyai_step_text():
    from paddle_tpu.models import JoyAIFlashConfig
    cfg = JoyAIFlashConfig(
        vocab_size=128, hidden_size=32, intermediate_size=48,
        moe_intermediate_size=16, num_hidden_layers=2,
        num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16,
        qk_nope_head_dim=8, qk_rope_head_dim=4, v_head_dim=8,
        n_routed_experts=8, num_experts_per_tok=2, num_experts_held=4)
    return _lower_lfm2(cfg, build_mesh({"dp": 1}), 2, 16,
                       remat_policy="ctx").compile().as_text()


def test_the_joyai_step_has_its_own_name(tiny_joyai_step_text):
    assert "HloModule jit_joyai_flash_spmd_train_step" \
        in tiny_joyai_step_text
    names = _op_names(tiny_joyai_step_text)
    assert any("/optimizer/" in n and "transpose(" not in n for n in names)
    # the other models' scopes are theirs
    assert not any(_under(n, BLOCK + (
        "unstack", "final_ln", "short_conv", "gqa_qkv", "gqa_out",
        "gdn_in", "gdn_scan", "gattn_qkv", "gattn_out")) for n in names)


@pytest.mark.parametrize("scope", JOYAI_SCOPES)
def test_a_joyai_scope_forward_and_backward(tiny_joyai_step_text, scope):
    names = _op_names(tiny_joyai_step_text)
    assert any(f"jvp({scope})" in n for n in names), scope
    assert any("transpose(" in n and _under(n, [scope]) for n in names), \
        scope


def test_the_module_s_block_carries_the_trunk_s_scopes_under_its_own(
        tiny_joyai_step_text):
    """The MTP module's block sits under the parent scope ``mtp`` and
    keeps the names of its parts: the reader gives an operation to the
    innermost scope a metric lists, so ``mla_proj_ms`` reads the module's
    projections with the trunk's and ``mtp_ms`` the module whole."""
    names = _op_names(tiny_joyai_step_text)
    mine = set(JOYAI_SCOPES)
    inside = [n for n in names if _under(n, ["mtp"])]
    for part in ("mla_q", "mla_kv", "mla_out", "moe_route", "moe_dispatch",
                 "moe_experts", "moe_combine", "shared_expert"):
        nested = [n for n in inside if _under(n, [part])]
        assert nested, part
        # (what of a part is run again under ``ctx`` is the compiler's
        # to choose; the norm and projections in front of attention
        # always are)
        for phase in ("forward", "backward") + (
                ("recompute",) if part == "mla_q" else ()):
            assert any(classify(n, mine) == (part, phase)
                       for n in nested), (part, phase)
        assert all(classify(n, {"mtp", "mtp_in"})[0] == "mtp"
                   for n in nested)
        # and the trunk's own stand outside it
        assert any(_under(n, [part]) and not _under(n, ["mtp"])
                   for n in names), part
    # neither the dense layer nor the heads are the module's
    assert not any(_under(n, ["dense_ffn"]) for n in inside)


def test_joyai_matmuls_sit_under_a_scope(tiny_joyai_step_text):
    """Every dot_general but attention's own (a sibling of its block's
    scopes; the module's under ``mtp`` alone) and the loss head's sits
    under one of the model's scopes."""
    names = _op_names(tiny_joyai_step_text)
    dots = [n for n in names if n.endswith("dot_general")]
    attention = [n for n in dots if "bqd,bkd->bqk" in n or "bqk,bkd->bqd" in n]
    parts = tuple(s for s in JOYAI_SCOPES if s != "mtp")
    assert attention and not any(_under(n, parts) for n in attention)
    assert any(_under(n, ["mtp"]) for n in attention)
    rest = [n for n in dots
            if n not in attention and not _under(n, ["loss_head"])]
    assert rest and all(_under(n, parts) for n in rest), \
        [n for n in rest if not _under(n, parts)]
    for scope in ("mla_q", "mla_kv", "mla_out", "dense_ffn", "mtp_in",
                  "moe_experts", "shared_expert"):
        assert any(_under(n, [scope]) for n in rest), scope
    # off the chip the head is the chunked one, under its scope
    assert any(_under(n, ["loss_head"]) for n in dots)


@pytest.fixture(scope="module")
def joyai_real_width_hlo(v5e):
    """The leading dense layer, one expert layer and the MTP module at
    the published widths, the cell's share (8 of 256 experts, V = 16 160)
    and the cell's B=2 x T=8192, for one v5e chip.  A v5e reports 128 MiB
    of VMEM, a described one nothing: the capacity is steered here."""
    from paddle_tpu.models import JoyAIFlashConfig
    from paddle_tpu.ops import pallas
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    mp = pytest.MonkeyPatch()
    for mod in (pallas, fa):
        mp.setattr(mod, "on_tpu", lambda: True)
    mp.setattr(fa, "_vmem_capacity", lambda: 128 << 20)
    try:
        cfg = JoyAIFlashConfig(vocab_size=16160, num_hidden_layers=2,
                               num_experts_held=8, moe_rows_factor=4.0)
        mesh = Mesh(np.asarray(v5e[:1]), ("dp",))
        return _lower_lfm2(cfg, mesh, 2, 8192,
                           remat_policy="ctx").compile().as_text()
    finally:
        mp.undo()


def test_every_joyai_mosaic_call_is_one_the_benchmark_finds(
        joyai_real_width_hlo):
    """The resident flash pair at q/k head size 192 over v head size 128
    (one forward and one fused backward a block, the module's too, none
    run again), the compiler's grouped expert matmuls and the fused loss
    head at V = 16 160, twice: each matches a pattern of exactly one of
    the cell's metric files, and no pattern of another cell's attention
    or loss-head file claims one.  The compile is also the proof that
    the pair fits VMEM at 1.5 lane tiles."""
    mosaic = _mosaic_calls(joyai_real_width_hlo)
    groups = {m: _patterns(m) for m in (
        "mla_attn_roofline", "joyai_moe_experts_roofline",
        "joyai_loss_head_events")}
    hits = {m: [c for c in mosaic if any(r.search(c) for r in rx)]
            for m, rx in groups.items()}
    attention = hits["mla_attn_roofline"]
    forward = [c for c in attention if groups["mla_attn_roofline"][0]
               .search(c)]
    backward = [c for c in attention if groups["mla_attn_roofline"][1]
                .search(c)]
    assert (len(forward), len(backward)) == (3, 3), \
        [c[:100] for c in attention]
    assert all("bf16[64,8192,128]" in c and "f32[64,8192,1]" in c
               for c in forward)
    assert all(c.count("bf16[64,8192,192]") >= 2 for c in backward)
    # the scope around the module renames its forward call; the patterns
    # go by the result shapes and find it all the same
    assert sorted(c.split(".")[0] for c in forward) == [
        "%jvp__", "%jvp__", "%jvp_mtp_"]
    assert not any(c.startswith("%rematted_computation")
                   for c in attention)
    # two expert layers (one block, the module): three grouped matmuls
    # forward, three recomputed and six backward each
    experts = hits["joyai_moe_experts_roofline"]
    assert sum(not c.startswith("%ragged-dot-metadata")
               for c in experts) == 24
    assert len(hits["joyai_loss_head_events"]) == 2
    assert sum(map(len, hits.values())) == len(mosaic), \
        [c[:100] for c in mosaic
         if not any(c in h for h in hits.values())]
    loop = groups["joyai_loss_head_events"][1]
    assert sum(bool(loop.search(i))
               for i in _instructions(joyai_real_width_hlo)) == 2
    # Of the other cells' files, those that name their shapes claim
    # nothing here: Qwen3-Next's attention (head size 256), GPT's forward
    # (one result, no lse) and every other loss head's loop (its own V).
    # GPT's backward and LFM2's stream patterns are generic in their
    # shapes and would find this pair too, and every loss head's forward
    # is the one call: each metric lists its own cell, so none reads
    # another cell's step
    for rx in (*_patterns("gattn_roofline"), _patterns("attn_roofline")[0]):
        assert not any(rx.search(c) for c in mosaic), rx.pattern
    for other in ("xent_head_roofline", "lfm2_loss_head_events",
                  "qwen3next_loss_head_events"):
        assert not any(_patterns(other)[1].search(i)
                       for i in _instructions(joyai_real_width_hlo)), other
    # and this cell's attention patterns find nothing in another's step
    for c in mosaic:
        assert "bf16[64,8192,256]" not in c and "bf16[128,8192,64]" not in c


@pytest.mark.parametrize("shape", HANDOVER_SHAPES)
def test_nothing_is_copied_between_the_mixer_s_kernel_families(
        qwen3_next_real_width_hlo, shape):
    """The convolution's kernels write q, k and v token-major, (B, T,
    heads x 128) — the form the rule's prep kernels read, the rule's loop
    writes ``o`` in and ``gdn_out`` takes its norm in (``_by_heads``: the
    axes in the tiles' order) — and the cotangents come back the same
    way: the step compiled for a v5e holds no ``copy``, ``reshape`` or
    ``transpose`` of its own of any of them.  With a ``(B, T, H, d)``
    array anywhere in between it holds one a direction: that is another
    tiling (with XLA's convolution, three of a row of ``o`` and ``do``
    under ``gdn_scan`` and three float32 ones under ``gdn_out``; with the
    convolution's kernels in front of a (B, T, H, d) entry, six more
    under no name and three under ``gdn_conv``)."""
    assert handover_copies(qwen3_next_real_width_hlo, shape) == []


@pytest.mark.parametrize("fixture", [
    "real_width_step_hlo", "lfm2_real_width_hlo", "joyai_real_width_hlo"],
    ids=["gpt", "lfm2", "joyai"])
def test_no_other_step_holds_the_delta_rule_mixer_s_kernels(request,
                                                            fixture):
    """``%gdn_conv_*`` and ``%delta_rule_*`` are the Qwen3-Next step's
    alone: LFM2's ``_short_conv`` keeps its XLA convolution."""
    hlo = request.getfixturevalue(fixture)
    hlo = hlo[1] if isinstance(hlo, tuple) else hlo
    assert not any(c.startswith(("%gdn_conv_", "%delta_rule_"))
                   for c in _mosaic_calls(hlo))
    assert "gdn_conv" not in hlo
