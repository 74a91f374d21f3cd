"""Chip-free compile for a v5e: the inner loop before spending chip time.

libtpu can hand out a device-less topology (``v5e:2x2`` -> four
``TPU v5 lite`` devices), and lowering + compiling against shardings on
those devices runs the real Mosaic / XLA-TPU compiler with no chip
attached.  ``jax.default_backend`` is patched to ``"tpu"`` so the code
takes its on-chip branches (kernels compiled, never interpreted).

Covered: every ``ops/pallas`` entry at chip_smoke.py's shapes, forward
and backward; the one-chip flagship train step with its Mosaic-call
count; and the same width over four chips — dp2 x mp2, pp2 x mp2 1F1B
and dp2 x sharding2 (ZeRO-2) — which need the kernels inside a
``shard_map`` ("Mosaic kernels cannot be automatically partitioned").
A compile proves lowering, tiling and VMEM fit; numerics need the chip
(``python chip_smoke.py`` through the chip tool).
"""
import functools
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import chip_smoke
from hlo_text import HANDOVER_SHAPES, handover_copies

pytestmark = pytest.mark.slow


@pytest.fixture(scope="module")
def v5e():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"no device-less TPU topology here: {e!r}")
    return list(topo.devices)


@pytest.fixture(autouse=True)
def on_chip_branches(monkeypatch):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")


def _mosaic_calls(lowered):
    return lowered.as_text().count("tpu_custom_call")


def _on(dev):
    sd = SingleDeviceSharding(dev)
    return lambda shape, dtype: jax.ShapeDtypeStruct(shape, dtype,
                                                     sharding=sd)


@pytest.mark.parametrize("B,T,regime", chip_smoke.ATTN_SHAPES)
def test_flash_attention_regimes_compile(v5e, B, T, regime):
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_stacked
    H, d = chip_smoke.ATTN_HEADS, chip_smoke.ATTN_HEAD_DIM
    S = _on(v5e[0])

    @jax.jit
    def fwd_bwd(qkv, g):               # stacked as the GPT block hands it
        out, vjp = jax.vjp(functools.partial(
            flash_attention_stacked, num_heads=H, causal=True), qkv)
        return out, vjp(g)[0]

    for dtype in (jnp.bfloat16, jnp.float32):
        before = pallas.selections().get(
            f"flash_attention.{regime}.mosaic", 0)
        low = fwd_bwd.lower(S((3, B, T, H * d), dtype),
                            S((B, T, H * d), dtype))
        assert pallas.selections()[
            f"flash_attention.{regime}.mosaic"] > before
        assert _mosaic_calls(low) >= 2
        low.compile()


def test_softmax_xent_compiles(v5e):
    from paddle_tpu.ops.pallas import softmax_xent as sx
    S = _on(v5e[0])
    N, D, V = 65536, 768, 30528
    low = jax.jit(jax.value_and_grad(
        lambda x, w, lab: sx.softmax_xent_loss(x, w, lab, False),
        (0, 1))).lower(S((N, D), jnp.bfloat16), S((D, V), jnp.bfloat16),
                       S((N,), jnp.int32))
    assert _mosaic_calls(low) == 1        # the backward is chunked XLA
    low.compile()
    jax.jit(sx.softmax_xent_dlogits).lower(
        S((1024, D), jnp.bfloat16), S((D, V), jnp.bfloat16),
        S((1024,), jnp.int32), S((1024,), jnp.float32),
        S((), jnp.float32)).compile()


@pytest.mark.parametrize("N,D,p", chip_smoke.LN_SHAPES)
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_ln_compiles(v5e, N, D, p, dtype):
    """Dropout (the hash's uint32 -> float32 cast) and a ragged row count
    (N=100) both used to be refused by Mosaic."""
    from paddle_tpu.ops.pallas.fused_ln import fused_ln_pallas
    S = _on(v5e[0])
    low = jax.jit(functools.partial(fused_ln_pallas, p=p, eps=1e-5)).lower(
        S((N, D), dtype), S((N, D), dtype), S((D,), dtype),
        S((D,), jnp.float32), S((D,), jnp.float32), S((), jnp.uint32))
    assert _mosaic_calls(low) == 1
    low.compile()


def _lower_step(devs, dims, batch, cfg_over=(), **kw):
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.models.gpt_spmd import (build_spmd_train_step,
                                            gpt_param_shardings,
                                            init_gpt_params)
    cfg = GPTConfig(**{**chip_smoke.GPT_DIMS, **dict(cfg_over)})
    mesh = Mesh(np.asarray(devs).reshape(tuple(dims.values())),
                tuple(dims))
    step, _ = build_spmd_train_step(
        cfg, mesh, compute_dtype=jnp.bfloat16, remat_policy="ctx", **kw)
    shapes = jax.eval_shape(
        lambda: init_gpt_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree.map(
        lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns),
        shapes, gpt_param_shardings(mesh, cfg))
    opt = {"m": params, "v": params,
           "step": jax.ShapeDtypeStruct(
               (), jnp.int32, sharding=NamedSharding(mesh, P()))}
    baxes = tuple(a for a in ("dp", "sharding") if dims.get(a, 1) > 1)
    ids = jax.ShapeDtypeStruct(
        (batch, cfg.max_seq_len), jnp.int32,
        sharding=NamedSharding(mesh, P(baxes or None)))
    return cfg, step.lower(params, opt, ids, ids)


def test_flagship_step_one_chip(v5e):
    """The recorded cell's exact shape: 12 forward + 12 backward flash
    kernels + the fused loss head — no kernel gave way to XLA math."""
    cfg, low = _lower_step(v5e[:1], {"dp": 1}, batch=128)
    assert _mosaic_calls(low) == 2 * cfg.num_layers + 1
    low.compile()


@pytest.mark.parametrize("dims,kw", [
    ({"dp": 2, "mp": 2}, {}),
    ({"pp": 2, "mp": 2}, dict(num_microbatches=4, schedule_mode="1F1B")),
    ({"dp": 2, "sharding": 2}, dict(sharding_stage=2)),
], ids=["dp2xmp2", "pp2xmp2-1F1B", "dp2xsharding2-zero2"])
def test_four_chip_meshes_compile(v5e, dims, kw):
    _, low = _lower_step(v5e, dims, batch=16, **kw)
    assert _mosaic_calls(low) > 0
    low.compile()


def test_sp_ring_attention_holds_no_mosaic_call(v5e):
    """Sequence parallelism compiles, but its per-shard attention is
    plain XLA math: ring attention never calls the flash kernels."""
    _, low = _lower_step(v5e, {"sp": 4}, batch=4,
                         cfg_over=dict(max_seq_len=4096, num_layers=2))
    assert _mosaic_calls(low) == 0
    low.compile()


def test_layer_api_attention_under_a_mesh(v5e):
    """``scaled_dot_product_attention`` traced inside
    ``pallas.kernel_mesh`` (what DataParallel.forward enters) runs its
    kernels per shard; with sharded operands and no mesh given the
    lowering refuses — it does not quietly take other math."""
    import paddle_tpu as paddle
    from paddle_tpu.ops import pallas
    mesh = Mesh(np.asarray(v5e).reshape(2, 2), ("dp", "mp"))
    spec = NamedSharding(mesh, P("dp", None, "mp", None))
    q = jax.ShapeDtypeStruct((8, 512, 12, 64), jnp.bfloat16, sharding=spec)

    def loss(q, k, v):
        out = paddle.nn.functional.scaled_dot_product_attention(
            paddle.Tensor(q), paddle.Tensor(k), paddle.Tensor(v),
            is_causal=True)
        return jnp.sum(out._data.astype(jnp.float32))

    def with_mesh(q, k, v):
        with pallas.kernel_mesh(mesh, batch_axes=("dp",),
                                head_axes=("mp",)):
            return jax.grad(loss, (0, 1, 2))(q, k, v)

    low = jax.jit(with_mesh).lower(q, q, q)
    # grad alone needs only the backward kernel (its residuals are the
    # raw inputs; the forward output is dead code here)
    assert _mosaic_calls(low) >= 1
    low.compile()
    with pytest.raises(NotImplementedError, match="shard_map"):
        jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, q, q)


# ---------------------------------------------------------------------------
# the Qwen3-Next cell's kernels at its sizes: head size 256, V = 18 992
# ---------------------------------------------------------------------------
def test_flash_attention_at_head_size_256_compiles(v5e, monkeypatch):
    """(B x H, T, d) = (64, 8192, 256) bf16 causal, forward and backward:
    K/V rows of 4 MB each stay resident (``stream_resident``) under a
    requested VMEM limit of 77 MB, taken while that is within three
    quarters of the chip's 128 MiB — which a v5e reports and a described
    one cannot, so the capacity is steered here."""
    import sys
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    monkeypatch.setattr(fa, "_vmem_capacity", lambda: 128 << 20)
    S = _on(v5e[0])

    @jax.jit
    def fwd_bwd(q, k, v, g):
        out, vjp = jax.vjp(functools.partial(flash_attention, causal=True),
                           q, k, v)
        return out, vjp(g)

    before = pallas.selections().get(
        "flash_attention.stream_resident.mosaic", 0)
    x = S((4, 8192, 16, 256), jnp.bfloat16)
    low = fwd_bwd.lower(x, x, x, x)
    assert pallas.selections()[
        "flash_attention.stream_resident.mosaic"] > before
    assert _mosaic_calls(low) == 2
    low.compile()


def test_loss_head_at_the_qwen3_next_cell_s_size_compiles(v5e):
    """(32 768, 2048) x 18 992: D = 2048 takes 512-row blocks, and the
    vocabulary (37 x 512 + 48) is padded to the kernel's column tile."""
    from paddle_tpu.ops.pallas import softmax_xent as sx
    S = _on(v5e[0])
    low = jax.jit(jax.value_and_grad(
        lambda x, w, lab: sx.softmax_xent_loss(x, w, lab, False),
        (0, 1))).lower(S((32768, 2048), jnp.bfloat16),
                       S((2048, 18992), jnp.bfloat16),
                       S((32768,), jnp.int32))
    assert _mosaic_calls(low) == 1
    low.compile()


def test_gated_delta_rule_at_the_qwen3_next_cell_s_size_compiles(v5e):
    """(4, 8192, 16 key / 32 value heads, d 128), chunk 64, bf16, a row at
    a time: the prep and the forward kernel; in the backward the prep
    again, the state pass, the loop's reverse pass and the prep's — six
    Mosaic calls, each within VMEM, and no inverse left to XLA."""
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.gated_delta_rule import gated_delta_rule
    S = _on(v5e[0])

    @jax.jit
    def fwd_bwd(q, k, v, g, beta, do):
        out, vjp = jax.vjp(functools.partial(gated_delta_rule, chunk=64),
                           q, k, v, g, beta)
        return out, vjp(do)

    before = pallas.selections().get("gated_delta_rule.mosaic", 0)
    qk = S((4, 8192, 16, 128), jnp.bfloat16)
    v = S((4, 8192, 32, 128), jnp.bfloat16)
    gate = S((4, 8192, 32), jnp.float32)
    low = fwd_bwd.lower(qk, qk, v, gate, gate, v)
    assert pallas.selections()["gated_delta_rule.mosaic"] == before + 1
    assert _mosaic_calls(low) == 6
    assert "triangular" not in low.compile().as_text().lower()


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_the_mixer_s_convolution_at_the_qwen3_next_cell_s_size_compiles(
        v5e, dtype):
    """qkv (4, 8192, 8192), four taps, 128-wide heads (bfloat16 is the
    cell's; float32 takes half the tile, the same bytes): the forward
    kernel (three outputs, the halo block) and the backward kernel (the
    carried ``dc``, the taps' gradient resident over the token tiles) —
    two Mosaic calls, each within the scoped VMEM."""
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.causal_conv import gated_causal_conv
    S = _on(v5e[0])

    @jax.jit
    def fwd_bwd(qkv, w, dq, dk, dv):
        out, vjp = jax.vjp(functools.partial(
            gated_causal_conv, n_qk=2048, head=128), qkv, w)
        return out, vjp((dq, dk, dv))

    before = pallas.selections().get("causal_conv.mosaic", 0)
    qk = S((4, 8192, 2048), dtype)
    low = fwd_bwd.lower(S((4, 8192, 8192), dtype), S((4, 8192), dtype),
                        qk, qk, S((4, 8192, 4096), dtype))
    assert pallas.selections()["causal_conv.mosaic"] == before + 1
    assert "causal_conv.interpret" not in pallas.selections()
    assert _mosaic_calls(low) == 2
    calls = _custom_calls(low.compile().as_text())
    # (outside a scope the compiler wraps the names: ``jvp_gdn_conv_fwd_``)
    assert sum("gdn_conv_fwd" in c for c in calls) == 1, calls
    assert sum("gdn_conv_bwd" in c for c in calls) == 1, calls


def _custom_calls(text):
    """The names of a compiled program's custom calls, less the trailing
    number (by the opcode: XLA also names a fusion that takes a kernel's
    result after the kernel)."""
    return re.findall(r"%(\w+)\.\d+ = [^\n]*? custom-call\(", text)


def _cell_step(v5e, monkeypatch, driver: str, config: str, workload: str):
    """The whole step of a benchmark cell — the configuration file's
    model at the cell's batch, bf16 over fp32 masters, the file's remat
    policy — compiled for one v5e.  -> (the compiled step, its number of
    parameters)."""
    import importlib
    import json
    import os
    import sys
    from paddle_tpu.models.gpt_spmd import build_spmd_train_step
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    monkeypatch.setattr(fa, "_vmem_capacity", lambda: 128 << 20)
    bench = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark")
    with open(os.path.join(bench, "configs", f"{config}.json")) as f:
        config = json.load(f)
    with open(os.path.join(bench, "workloads", f"{workload}.json")) as f:
        traffic = json.load(f)["traffic"]
    cfg = importlib.import_module(
        f"benchmark.drivers.{driver}").model_config(config)
    mesh = Mesh(np.asarray(v5e[:1]), ("dp",))
    step, _ = build_spmd_train_step(
        cfg, mesh, compute_dtype=jnp.bfloat16,
        remat_policy=config["assumed"]["remat_policy"])
    parts = cfg.spmd_parts(mesh)
    shapes = jax.eval_shape(parts.init, jax.random.PRNGKey(0))
    n_params = sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes))
    params = jax.tree.map(
        lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=ns),
        shapes, parts.shardings)
    rep = NamedSharding(mesh, P())
    opt = {"m": params, "v": params,
           "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}
    ids = jax.ShapeDtypeStruct((traffic["batch"], traffic["seq_len"]),
                               jnp.int32, sharding=rep)
    return step.lower(params, opt, ids, ids).compile(), n_params


def _held(compiled, n_params: int) -> int:
    """Arguments + temporaries + the 4 B a parameter the benchmark's check
    keeps beside the step (a copy of the initial weights, through the
    first three steps)."""
    mem = compiled.memory_analysis()
    return mem.argument_size_in_bytes + mem.temp_size_in_bytes \
        + 4 * n_params


def test_the_qwen3_next_step_fits_the_chip_at_the_cell_s_size(
        v5e, monkeypatch, capsys):
    """The whole step of ``qwen3-next-80b-a3b.train-t8192`` (424.3 M
    parameters, B = 4 x T = 8192) compiled for one v5e: six delta-rule
    kernels a layer in three layers, no ``triangular_solve``; the
    convolution's pair three times a layer (forward, the forward again in
    the recompute, backward); and what the step holds stays under the
    chip's ``bytes_limit`` of 16.91 GB and is no more than with XLA's
    convolution (PR 39's step, compiled the same way: 13.31 GB; with
    XLA's prep too, PR 37's, 14.502)."""
    compiled, n_params = _cell_step(
        v5e, monkeypatch, "qwen3_next_train", "qwen3-next-80b-a3b",
        "qwen3-next-80b-a3b.train-t8192")
    text = compiled.as_text()
    calls = _custom_calls(text)
    assert sum(c.startswith("delta_rule_") for c in calls) == 18
    assert sorted(c for c in calls if c.startswith("gdn_conv_")) \
        == ["gdn_conv_bwd"] * 3 + ["gdn_conv_fwd"] * 6
    assert "triangular" not in text.lower()
    # q, k, v, o and their cotangents go token-major from kernel to
    # kernel and into ``gdn_out``: no relayout of any of them
    for shape in HANDOVER_SHAPES:
        assert handover_copies(text, shape) == [], shape
    held = _held(compiled, n_params)
    with capsys.disabled():
        print(f"\nqwen3-next step, chip-free: {held / 1e9:.3f} GB held "
              f"(the parent's, with XLA's convolution: 13.31 GB)")
    assert 11e9 < held < 13.5e9, held


# ---------------------------------------------------------------------------
# the JoyAI-LLM-Flash cell: the kernels at 192 over 128, and the whole step
# at the cell's size against the chip's memory
# ---------------------------------------------------------------------------
def test_flash_attention_at_unequal_head_sizes_compiles(v5e, monkeypatch):
    """(B x H, T) = (64, 8192), q and k 192 wide (1.5 lane tiles), v 128,
    bf16 causal, forward and backward: the resident pair takes it under a
    requested VMEM limit of 61 MB (K rows padded to 256 lanes, V rows
    128), a forward and a fused backward."""
    import sys
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas.flash_attention import flash_attention
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    monkeypatch.setattr(fa, "_vmem_capacity", lambda: 128 << 20)
    S = _on(v5e[0])

    @jax.jit
    def fwd_bwd(q, k, v, g):
        out, vjp = jax.vjp(functools.partial(flash_attention, causal=True),
                           q, k, v)
        return out, vjp(g)

    before = pallas.selections().get(
        "flash_attention.stream_resident.mosaic", 0)
    qk = S((2, 8192, 32, 192), jnp.bfloat16)
    v = S((2, 8192, 32, 128), jnp.bfloat16)
    low = fwd_bwd.lower(qk, qk, v, v)
    assert pallas.selections()[
        "flash_attention.stream_resident.mosaic"] > before
    assert "flash_attention.xla" not in pallas.selections()
    assert _mosaic_calls(low) == 2
    low.compile()


def test_the_joyai_step_fits_the_chip_at_the_cell_s_size(v5e, monkeypatch):
    """The whole step of ``joyai-llm-flash.train-t8192`` — the
    configuration file's model (491.7 M parameters: five layers and the
    MTP module), B = 2 x T = 8192, bf16 over fp32 masters, ``ctx`` remat —
    compiled for one v5e: 6 + 6 flash calls and the loss head twice, and
    what the step holds (``_held``) stays under the chip's ``bytes_limit``
    of 16.91 GB with gigabytes to spare (11.42 + 1.97 = 13.38 GB, PR
    37)."""
    compiled, n_params = _cell_step(
        v5e, monkeypatch, "joyai_train", "joyai-llm-flash",
        "joyai-llm-flash.train-t8192")
    assert n_params == 491697408
    text = compiled.as_text()
    assert text.count("bf16[64,8192,192]{2,1,0:T(8,128)(2,1)}, "
                      "bf16[64,8192,192]") >= 6       # the fused backwards
    held = _held(compiled, n_params)
    assert 11e9 < held < 15.9e9, held
