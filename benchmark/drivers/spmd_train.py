"""Driver of a cell that trains a GPT through the program's functional
step: ``build_spmd_train_step`` over a one-device mesh, the call sequence
``chip_smoke.phase_train`` ran on the chip.

Set-up builds one object — the compiled step with its state — from the
benchmark's seeded weights, drives it through the first ``check_steps``
steps on pool batches that all differ, records what came out (each loss,
the first gradient's leaf norms from the optimizer's state, the leaf norms
of the parameters' change), and hands that same object to the window.
"""
from benchmark.drivers._common import (
    no_interpreted_kernels, steps_until, window_result)


class State:
    pass


def setup(ctx):
    import time
    t = [time.perf_counter()]

    def phase(name):
        t.append(time.perf_counter())
        ctx.phases[name] = t[-1] - t[-2]

    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.models.gpt_spmd import build_spmd_train_step

    cfg, traffic, ref = ctx.config, ctx.traffic, ctx.reference
    assumed = cfg["assumed"]
    opt = assumed["optimizer"]
    gcfg = GPTConfig(vocab_size=cfg["vocab_size"], hidden_size=cfg["n_embd"],
                     num_layers=cfg["n_layer"], num_heads=cfg["n_head"],
                     max_seq_len=cfg["n_positions"])
    mesh = build_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, _init_fn = build_spmd_train_step(
        gcfg, mesh, compute_dtype=jnp.dtype(assumed["compute_dtype"]),
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
    for i in range(traffic["check_steps"]):
        ids, labels = st.pool[st.next % len(st.pool)]
        st.next += 1
        loss, params, opt_state = step(params, opt_state, ids, labels)
        ev["loss"].append(float(loss))
        if i == 0:
            # the first gradient as the optimizer got it: m1 / (1 - beta1)
            ev["grad_norm"] = jax.device_get(norms(opt_state["m"]))
            phase("first_step")
    ev["change_norm"] = jax.device_get(diff_norms(params, p0))
    del p0
    phase("later_steps")
    st.produced = ev
    st.params, st.opt_state = params, opt_state

    no_interpreted_kernels("spmd_train")
    return st


def window(st, seconds):
    """Steps until the deadline, one step kept in flight; counts every
    step dispatched."""
    import jax
    state = [st.params, st.opt_state]
    st.params = st.opt_state = None

    def dispatch():
        ids, labels = st.pool[st.next % len(st.pool)]
        st.next += 1
        loss, state[0], state[1] = st.step(state[0], state[1], ids, labels)
        return loss

    steps, bad, window_s = steps_until(
        dispatch, seconds, lambda: jax.block_until_ready(state[0]))
    st.params, st.opt_state = state
    return window_result(steps, bad, window_s, st.pool[0][0].shape[0])


def release(st):
    st.params = st.opt_state = st.pool = st.step = None
