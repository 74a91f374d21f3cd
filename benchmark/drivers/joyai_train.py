"""Driver of a cell that trains a JoyAI-LLM-Flash through the program's
functional step: ``build_spmd_train_step`` given a ``JoyAIFlashConfig``
over a one-device mesh.  The sequence is ``drivers/lfm2_train.py``'s —
the first steps through the window's own call and feed, the device's
counters read for the checked steps and once after the window, a step
with ``moe_overflow`` counted as ``failed`` — and its window and release
are used as they are.  What differs is the program's configuration of the
configuration file and two more counters of the checked steps: the step
returns the two terms of its loss, ``loss_main`` and ``loss_mtp`` (the
multi-token-prediction module's), and ``info.window.moe.checked_steps``
carries both; the check compares their weighted sum, the step's loss.
"""
from benchmark.drivers._common import no_interpreted_kernels
from benchmark.drivers.lfm2_train import (  # noqa: F401
    State, _counters, release, window)


def model_config(cfg):
    """The program's configuration of a configuration file.  The keys
    that select a mechanism and have one value the program computes are
    checked, not read past."""
    from paddle_tpu.models.joyai_flash import JoyAIFlashConfig
    fixed = {"scoring_func": "sigmoid", "topk_method": "noaux_tc",
             "n_group": 1, "topk_group": 1, "norm_topk_prob": True,
             "rope_interleave": True, "rope_scaling": None,
             "moe_layer_freq": 1, "tie_word_embeddings": False,
             "attention_bias": False}
    other = {k: cfg[k] for k, v in fixed.items() if cfg.get(k, v) != v}
    if other:
        raise NotImplementedError(
            f"the JoyAI-LLM-Flash model computes {fixed}; the "
            f"configuration asks for {other}")
    dep = cfg.get("deployment", {})
    assumed = cfg.get("assumed", {})
    return JoyAIFlashConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        num_hidden_layers=cfg["num_hidden_layers"],
        first_k_dense_replace=cfg["first_k_dense_replace"],
        num_attention_heads=cfg["num_attention_heads"],
        q_lora_rank=cfg["q_lora_rank"], kv_lora_rank=cfg["kv_lora_rank"],
        qk_nope_head_dim=cfg["qk_nope_head_dim"],
        qk_rope_head_dim=cfg["qk_rope_head_dim"],
        v_head_dim=cfg["v_head_dim"], rope_theta=float(cfg["rope_theta"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        n_routed_experts=dep.get("router_width", cfg["n_routed_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"],
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        num_nextn_predict_layers=cfg["num_nextn_predict_layers"],
        mtp_loss_weight=assumed.get("mtp_loss_weight", 0.3),
        num_experts_held=cfg["n_routed_experts"],
        first_expert=dep.get("first_expert", 0),
        moe_rows_factor=assumed.get("moe_rows_factor"))


def _checked(steps):
    """``_counters`` of the checked steps, each with the two terms of its
    loss."""
    import jax
    steps = jax.device_get(steps)
    return [dict(c, **{k: float(v) for k, v in step.items()
                       if k.startswith("loss_")})
            for c, step in zip(_counters(steps), steps)]


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
    st.checked_counters = _checked(checked)
    phase("later_steps")
    st.produced = ev
    st.params, st.opt_state = params, opt_state

    no_interpreted_kernels("joyai_train")
    return st
