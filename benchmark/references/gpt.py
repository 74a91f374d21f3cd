"""Plain reference of the GPT-2 training step: forward, loss, gradients
and the AdamW update in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision — no kernels, no cache, no program code.

Follows the published GPT-2 block (pre-LayerNorm, learned positions, causal
softmax attention scaled by 1/sqrt(head size), ``gelu_new`` FFN of width
4 n_embd).  Stated departures (the configuration file lists them): the
output head is a separate matrix and not tied to ``wte``; no dropout.

Parameter layout (the one the program's step takes, so that one set of
seeded weights serves both sides): ``wte`` (V, D), ``wpe`` (P, D),
``blocks`` with every layer stacked on a leading axis — ``qkv_w``
(L, D, 3, D) with heads contiguous in the last axis, ``qkv_b`` (L, 3, D),
``out_w`` (L, D, D), ``up_w`` (L, D, F), ``down_w`` (L, F, D), their
biases, ``ln1``/``ln2`` gains and biases — then ``ln_f_g``, ``ln_f_b``,
``head_w`` (D, V).

The batch is walked in blocks of rows and each layer is recomputed in the
backward pass (``jax.checkpoint``), so that float32 at the timed sizes
fits one chip.  ``precision`` selects what the matrix multiplications
see: ``float32`` (the reference), ``bfloat16``, or ``fp8`` (operands
rounded to e4m3 with one scale per tensor — the control: the step below
the configuration's bfloat16 that would tempt a later PR).
"""
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST


def _dims(config):
    D, L, V = config["n_embd"], config["n_layer"], config["vocab_size"]
    return D, L, V, config.get("n_inner") or 4 * D, config["n_positions"]


@partial(jax.jit, static_argnums=(1, 2, 3, 4, 5))
def _init(key, D, L, V, F, P):
    ks = iter(jax.random.split(key, 20))

    def normal(shape, std):
        return jax.random.normal(next(ks), shape, jnp.float32) * std

    def glorot(shape):
        return normal(shape, np.sqrt(2.0 / (shape[-2] + shape[-1])))

    def gain(shape):
        return 1.0 + normal(shape, 0.02)

    blocks = {
        "ln1_g": gain((L, D)), "ln1_b": normal((L, D), 0.02),
        "qkv_w": glorot((L, D, 3 * D)).reshape(L, D, 3, D),
        "qkv_b": normal((L, 3, D), 0.02),
        "out_w": glorot((L, D, D)), "out_b": normal((L, D), 0.02),
        "ln2_g": gain((L, D)), "ln2_b": normal((L, D), 0.02),
        "up_w": glorot((L, D, F)), "up_b": normal((L, F), 0.02),
        "down_w": glorot((L, F, D)), "down_b": normal((L, D), 0.02),
    }
    return {"wte": normal((V, D), 0.02), "wpe": normal((P, D), 0.02),
            "blocks": blocks,
            "ln_f_g": gain((D,)), "ln_f_b": normal((D,), 0.02),
            "head_w": glorot((D, V))}


def init_params(config, seed):
    """Seeded float32 weights, made on the device in one jitted call."""
    return _init(jax.random.PRNGKey(seed), *_dims(config))


def make_batches(config, traffic, seed):
    """The cell's token pool from the seed, on the host: ``pool`` batches
    of (ids, labels), labels the next token of the same packed row."""
    B, T, n = traffic["batch"], traffic["seq_len"], traffic["pool"]
    rs = np.random.RandomState(seed % (2 ** 32))
    tok = rs.randint(0, config["vocab_size"], (n, B, T + 1)).astype(np.int32)
    return [(tok[i, :, :-1], tok[i, :, 1:]) for i in range(n)]


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _fp8(x):
    """x rounded to e4m3 with one scale for the tensor.  The backward
    pass sees the rounding as the identity (straight through): the
    cotangent stays in float32 and meets the rounded operands."""
    s = jnp.max(jnp.abs(x)).astype(jnp.float32) / 448.0
    s = jnp.where(s > 0, s, 1.0)
    q = (x.astype(jnp.float32) / s).astype(jnp.float8_e4m3fn) \
        .astype(jnp.float32) * s
    return x + lax.stop_gradient(q - x)


def _einsum(precision, spec, a, b):
    if precision == "fp8":
        a, b = _fp8(a), _fp8(b)
    elif precision == "bfloat16":
        return jnp.einsum(spec, a.astype(jnp.bfloat16),
                          b.astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)
    elif precision != "float32":
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.einsum(spec, a, b, precision=HIGHEST)


def _layernorm(x, g, b, eps):
    mu = jnp.mean(x, -1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), -1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        np.sqrt(2.0 / np.pi) * (x + 0.044715 * x ** 3)))


def _block(p, x, n_head, eps, mm):
    B, T, D = x.shape
    hd = D // n_head
    y = _layernorm(x, p["ln1_g"], p["ln1_b"], eps)
    qkv = mm("btd,dse->btse", y, p["qkv_w"]) + p["qkv_b"]
    q, k, v = (qkv[:, :, i].reshape(B, T, n_head, hd) for i in range(3))
    s = mm("bthd,bshd->bhts", q, k) / np.sqrt(hd)
    mask = jnp.tril(jnp.ones((T, T), bool))
    s = jnp.where(mask, s, -jnp.inf)
    a = jax.nn.softmax(s, axis=-1)
    ctx = mm("bhts,bshd->bthd", a, v).reshape(B, T, D)
    x = x + mm("btd,de->bte", ctx, p["out_w"]) + p["out_b"]
    y = _layernorm(x, p["ln2_g"], p["ln2_b"], eps)
    up = _gelu_new(mm("btd,df->btf", y, p["up_w"]) + p["up_b"])
    return x + mm("btf,fd->btd", up, p["down_w"]) + p["down_b"]


def summed_loss(params, ids, labels, config, precision="float32"):
    """Summed next-token cross-entropy of a block of rows."""
    mm = partial(_einsum, precision)
    eps, n_head = config["layer_norm_epsilon"], config["n_head"]
    T = ids.shape[1]
    x = params["wte"][ids] + params["wpe"][:T][None]
    layer = jax.checkpoint(
        lambda p, h: _block(p, h, n_head, eps, mm))
    x, _ = lax.scan(lambda h, p: (layer(p, h), None), x, params["blocks"])
    x = _layernorm(x, params["ln_f_g"], params["ln_f_b"], eps)
    logits = mm("btd,dv->btv", x, params["head_w"])
    lse = jax.nn.logsumexp(logits, axis=-1)
    at = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - at)


# ---------------------------------------------------------------------------
# the training step and its evidence
# ---------------------------------------------------------------------------
def _adamw(params, grads, m, v, t, o):
    b1, b2 = o["beta1"], o["beta2"]
    lr, wd, eps = o["learning_rate"], o["weight_decay"], o["eps"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree.map(
        lambda p, m_, v_: (1 - lr * wd) * p
        - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps), params, m, v)
    return params, m, v


def evidence(config, traffic, seed, leaf_norms,
             precision="float32", fault=None, rows=4):
    """The reference's evidence for a cell: weights and batches made
    from the seed here, nothing taken from the program."""
    return train_evidence(
        config, traffic, init_params(config, seed),
        make_batches(config, traffic, seed), leaf_norms,
        precision=precision, fault=fault, rows=rows)


def train_evidence(config, traffic, params, batches, leaf_norms,
                   precision="float32", fault=None, rows=4):
    """Runs the first ``check_steps`` training steps from ``params`` on
    ``batches`` and returns the evidence the harness compares
    (``checks/training.py``).  ``fault`` plants one of the faults a
    training cell can have: ``half_batch`` (the second half of every
    batch left out, the mean taken over the rest) or ``state_unchanged``
    (the step returns its state as it got it)."""
    opt = config["assumed"]["optimizer"]
    steps = traffic["check_steps"]
    vg = jax.jit(jax.value_and_grad(partial(
        summed_loss, config=config, precision=precision)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    update = jax.jit(lambda p, g, m, v, t, n: _adamw(
        p, jax.tree.map(lambda x: x / n, g), m, v, t, opt),
        donate_argnums=(1, 2, 3))
    norms = jax.jit(leaf_norms)
    diff_norms = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))
    p0 = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    out = {"loss": []}
    for t in range(1, steps + 1):
        ids, labels = batches[t - 1]
        if fault == "half_batch":
            ids, labels = ids[:len(ids) // 2], labels[:len(labels) // 2]
        n_tok = float(ids.shape[0] * ids.shape[1])
        total, grads = 0.0, None
        for r in range(0, ids.shape[0], rows):
            l, g = vg(params, jnp.asarray(ids[r:r + rows]),
                      jnp.asarray(labels[r:r + rows]))
            total += float(l)
            grads = g if grads is None else add(grads, g)
        out["loss"].append(total / n_tok)
        if fault != "state_unchanged":
            params, m, v = update(params, grads, m, v, float(t), n_tok)
        if t == 1:
            # the first gradient as the optimizer got it, from its state
            # after one step: m1 = (1 - beta1) g
            out["grad_norm"] = jax.device_get(norms(jax.tree.map(
                lambda x: x / (1 - opt["beta1"]), m)))
        del grads
    out["change_norm"] = jax.device_get(diff_norms(params, p0))
    return out
