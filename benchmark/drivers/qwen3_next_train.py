"""Driver of a cell that trains a Qwen3-Next through the program's
functional step: ``build_spmd_train_step`` given a ``Qwen3NextConfig``
over a one-device mesh.  The sequence is ``drivers/lfm2_train.py``'s —
the first steps through the window's own call and feed, the device's
counters (``moe_counts``, ``moe_overflow``) read for the checked steps
and once after the window, a step with overflow counted as ``failed`` —
and its window, counters and release are used as they are; what differs
is the program's configuration of the configuration file.
"""
from benchmark.drivers._common import no_interpreted_kernels
from benchmark.drivers.lfm2_train import (  # noqa: F401
    State, _counters, release, window)


def model_config(cfg):
    """The program's configuration of a configuration file."""
    from paddle_tpu.models.qwen3_next import Qwen3NextConfig
    dep = cfg.get("deployment", {})
    assumed = cfg.get("assumed", {})
    return Qwen3NextConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        full_attention_interval=cfg["full_attention_interval"],
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"],
        partial_rotary_factor=cfg["partial_rotary_factor"],
        rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        linear_num_key_heads=cfg["linear_num_key_heads"],
        linear_num_value_heads=cfg["linear_num_value_heads"],
        linear_key_head_dim=cfg["linear_key_head_dim"],
        linear_value_head_dim=cfg["linear_value_head_dim"],
        linear_conv_kernel_dim=cfg["linear_conv_kernel_dim"],
        num_experts=dep.get("router_width", cfg["num_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        shared_expert_intermediate_size=cfg[
            "shared_expert_intermediate_size"],
        norm_topk_prob=cfg.get("norm_topk_prob", True),
        num_experts_held=cfg["num_experts"],
        first_expert=dep.get("first_expert", 0),
        moe_rows_factor=assumed.get("moe_rows_factor"),
        gdn_chunk=assumed.get("gdn_chunk", 64))


def setup(ctx):
    import time
    t = [time.perf_counter()]

    def phase(name):
        t.append(time.perf_counter())
        ctx.phases[name] = t[-1] - t[-2]

    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models.gpt_spmd import build_spmd_train_step

    cfg, traffic, ref = ctx.config, ctx.traffic, ctx.reference
    assumed = cfg["assumed"]
    opt = assumed["optimizer"]
    mesh = build_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, _init_fn = build_spmd_train_step(
        model_config(cfg), mesh,
        compute_dtype=jnp.dtype(assumed["compute_dtype"]),
        remat_policy=assumed["remat_policy"],
        learning_rate=opt["learning_rate"],
        weight_decay=opt["weight_decay"])

    st = State()
    st.step = step
    phase("build_step")
    params = ref.init_params(cfg, ctx.seed)
    # one call makes the initial weights' copy (the step donates its
    # arguments) and the optimizer's zeros
    p0, m, v = jax.jit(lambda p: (
        jax.tree.map(jnp.copy, p), jax.tree.map(jnp.zeros_like, p),
        jax.tree.map(jnp.zeros_like, p)))(params)
    opt_state = {"m": m, "v": v, "step": jnp.zeros((), jnp.int32)}
    st.pool = [(jax.device_put(i), jax.device_put(l))
               for i, l in ref.make_batches(cfg, traffic, ctx.seed)]
    jax.block_until_ready((p0, opt_state, st.pool))
    phase("weights_and_pool")

    # the first steps, through the window's own call and feed
    leaf_norms = ctx.check.leaf_norms_for(ctx.cell)
    norms = jax.jit(lambda m: leaf_norms(jax.tree.map(
        lambda x: x / (1 - opt["beta1"]), m)))
    diff_norms = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))
    ev = {"loss": []}
    st.next = 0
    checked = []
    for i in range(traffic["check_steps"]):
        ids, labels = st.pool[st.next % len(st.pool)]
        st.next += 1
        loss, params, opt_state, counters = step(params, opt_state, ids,
                                                 labels)
        ev["loss"].append(float(loss))
        checked.append(counters)
        if i == 0:
            # the first gradient as the optimizer got it: m1 / (1 - beta1)
            ev["grad_norm"] = jax.device_get(norms(opt_state["m"]))
            phase("first_step")
    ev["change_norm"] = jax.device_get(diff_norms(params, p0))
    del p0
    st.checked_counters = _counters(checked)
    phase("later_steps")
    st.produced = ev
    st.params, st.opt_state = params, opt_state

    no_interpreted_kernels("qwen3_next_train")
    return st
