"""Driver of a cell that trains an LFM2-MoE through the program's
functional step: ``build_spmd_train_step`` given an ``Lfm2MoeConfig``
over a one-device mesh — the sequence of ``drivers/spmd_train.py`` for a
model whose step also hands back counters from the device (assignments
per held expert of every expert layer, and ``moe_overflow``: assignments
that found no room in the routed-row buffer).

The counters are read for the checked steps and once after the window,
never inside it; they go to ``info.window.moe`` of the result line.  A
step whose ``moe_overflow`` is not 0 counts as ``failed``.
"""
from benchmark.drivers._common import (
    no_interpreted_kernels, steps_until, window_result)


class State:
    pass


def model_config(cfg):
    """The program's configuration of a configuration file."""
    from paddle_tpu.models.lfm2_moe import Lfm2MoeConfig
    dep = cfg.get("deployment", {})
    return Lfm2MoeConfig(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        intermediate_size=cfg["intermediate_size"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        layer_types=tuple(cfg["layer_types"]),
        num_dense_layers=cfg["num_dense_layers"],
        num_experts=dep.get("router_width", cfg["num_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        num_experts_held=cfg["num_experts"],
        first_expert=dep.get("first_expert", 0),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        conv_L_cache=cfg["conv_L_cache"], norm_eps=cfg["norm_eps"],
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        routed_scaling_factor=float(cfg["routed_scaling_factor"]),
        moe_rows_factor=cfg["assumed"].get("moe_rows_factor"))


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

    no_interpreted_kernels("lfm2_train")
    return st


def _counters(steps):
    """The device's counters of some steps, read now: per step the
    assignments per held expert of each expert layer and the overflow."""
    import jax
    return [{"moe_counts": c["moe_counts"].tolist(),
             "moe_overflow": int(c["moe_overflow"])}
            for c in jax.device_get(steps)]


def window(st, seconds):
    """Steps until the deadline, one step kept in flight; counts every
    step dispatched.  Every step's overflow and the last step's counts
    stay on the device until the window has closed."""
    import jax
    state = [st.params, st.opt_state]
    st.params = st.opt_state = None
    overflow, last = [], [None]

    def dispatch():
        ids, labels = st.pool[st.next % len(st.pool)]
        st.next += 1
        loss, state[0], state[1], last[0] = st.step(state[0], state[1],
                                                    ids, labels)
        overflow.append(last[0]["moe_overflow"])
        return loss

    steps, bad, window_s = steps_until(
        dispatch, seconds, lambda: jax.block_until_ready(state[0]))
    st.params, st.opt_state = state
    overflow = [int(o) for o in jax.device_get(overflow)]
    overflowed = sum(o > 0 for o in overflow) + sum(
        c["moe_overflow"] > 0 for c in st.checked_counters)
    out = window_result(steps, bad + overflowed, window_s,
                        st.pool[0][0].shape[0])
    out["moe"] = {"checked_steps": st.checked_counters,
                  "last_step": _counters(last)[0],
                  "overflow_in_window": sum(overflow),
                  "steps_with_overflow": overflowed}
    return out


def release(st):
    st.params = st.opt_state = st.pool = st.step = None
