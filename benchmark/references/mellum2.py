"""Plain reference of the Mellum2 training step: forward, loss, gradients
and the AdamW update in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision — no kernels, no sort, no program code.

The equations (config keys in backticks; ``RMS(x; g) = g x rsqrt(mean(x^2)
+ rms_norm_eps)``; no bias anywhere):

- ``x0 = wte[ids]``; layer ``l`` (0-based, the first
  ``num_hidden_layers`` of ``layer_types``): ``h = x + Attn_l(RMS(x;
  op_norm))``, ``x' = h + MoE(RMS(h; ffn_norm))``; output ``RMS(x_L;
  out_norm) head_w``; loss = mean next-token cross-entropy, no auxiliary
  loss.
- ``Attn_l``: ``q = z W_q`` (``num_attention_heads`` heads of
  ``head_dim``), ``k = z W_k``, ``v = z W_v`` (``num_key_value_heads``
  heads); RoPE over the whole head, components ``(i, i + hd/2)`` turned
  by ``t inv_i``, with the section of ``rope_parameters`` that
  ``layer_types[l]`` names: ``default`` — ``inv_i = rope_theta^(-2i/hd)``;
  ``yarn`` — ``inv_i = inv_i / factor (1 - e_i) + inv_i e_i`` with ``e_i
  = 1 - clamp((i - lo) / (hi - lo), 0, 1)``, ``lo = floor(c(beta_fast))``,
  ``hi = ceil(c(beta_slow))``, ``c(r) = hd ln(original_max_position_
  embeddings / (2 pi r)) / (2 ln rope_theta)``, and cos and sin times
  ``attention_factor``.  ``softmax(q k^T / sqrt(hd) + M) v`` with KV head j
  serving query heads ``j g .. j g + g - 1``; ``M`` keeps key j for query
  i where ``0 <= i - j``, and on a ``sliding_attention`` layer also ``i -
  j < sliding_window`` — written here as the explicit mask, no kernel;
  ``ctx W_o``.
- MoE: ``p = softmax(z W_r)`` over the router's whole width in float32,
  the top ``num_experts_per_tok`` chosen and renormalised
  (``norm_topk_prob``), the chosen HELD experts' ``(silu(z W1_e) * z W3_e)
  W2_e`` summed with their weights — ``references/qwen3_next.py``'s
  ``routed_part``, the same routing, without its shared expert.

Stated departures and assumptions (the configuration file lists them):
the chip's share of the experts and of the vocabulary; no MTP module; the
initial values.

Parameter layout (the program's, so that one set of seeded weights serves
both sides): ``wte`` (V, D), ``layers`` a list of one dict per layer —
``op_norm``, ``ffn_norm`` (D,), ``q_w`` (D, H hd), ``k_w``, ``v_w`` (D, K
hd), ``o_w`` (H hd, D), ``router_w`` (D, E), ``w1``, ``w3`` (held, D, F),
``w2`` (held, F, D) — then ``out_norm`` (D,), ``head_w`` (D, V).

The batch is walked in blocks of rows, each layer is recomputed in the
backward pass (``jax.checkpoint``), attention runs one query head and
one block of ``QUERY_BLOCK`` queries at a time against all keys under the
mask, and the experts one at a time, so that float32 at the timed sizes
fits one chip.  ``precision`` selects what the matrix multiplications see
(the router always float32): ``float32`` (the reference), ``bfloat16``,
or ``fp8`` (operands rounded to e4m3 with one scale per tensor — the
control).
"""
from functools import partial
import math

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# one definition of the precisions and of the token pool for every
# family; Qwen3-Next's routing (softmax over the router's width, top-k,
# renormalised, the held experts one at a time) and AdamW
from benchmark.references.gpt import (  # noqa: F401
    HIGHEST, _einsum, make_batches)
from benchmark.references.qwen3_next import adamw, routed_part

QUERY_BLOCK = 1024      # queries a block of the attention


def layer_window(config, l):
    """Layer ``l``'s sliding window, or None where it sees every key."""
    if config["layer_types"][l] == "sliding_attention" \
            and config.get("use_sliding_window", True):
        return config["sliding_window"]
    return None


def init_params(config, seed):
    """Seeded float32 weights: normal(0, 0.02), gains 1, the input
    embedding normal(0, 1) (the configuration file's ``assumed.init``)."""
    c = config
    D, V, hd = c["hidden_size"], c["vocab_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * hd, c["num_key_value_heads"] * hd
    held, F = c["num_experts"], c["moe_intermediate_size"]
    E = c.get("deployment", {}).get("router_width", held)
    L = c["num_hidden_layers"]

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 8 * L + 2))

        def normal(*shape):
            return jax.random.normal(next(keys), shape, jnp.float32) * 0.02

        layers = [{"op_norm": jnp.ones((D,)), "ffn_norm": jnp.ones((D,)),
                   "q_w": normal(D, q), "k_w": normal(D, kv),
                   "v_w": normal(D, kv), "o_w": normal(q, D),
                   "router_w": normal(D, E), "w1": normal(held, D, F),
                   "w3": normal(held, D, F), "w2": normal(held, F, D)}
                  for _ in range(L)]
        wte = jax.random.normal(next(keys), (V, D), jnp.float32)
        return {"wte": wte, "layers": layers,
                "out_norm": jnp.ones((D,)), "head_w": normal(D, V)}

    return make(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _norm(x, w, eps):
    return w * x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def inv_freq(config, kind):
    """(inverse frequencies (hd/2,) float64, the factor on cos and sin) of
    a layer kind's section of ``rope_parameters``."""
    hd = config["head_dim"]
    rp = config["rope_parameters"][kind]
    theta = float(rp["rope_theta"])
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    if rp["rope_type"] == "default":
        return inv, 1.0
    assert rp["rope_type"] == "yarn", rp

    def c(r):
        return hd * math.log(rp["original_max_position_embeddings"]
                             / (2 * math.pi * r)) / (2 * math.log(theta))

    lo = max(math.floor(c(rp["beta_fast"])), 0)
    hi = min(math.ceil(c(rp["beta_slow"])), hd - 1)
    e = 1 - np.clip((np.arange(hd // 2) - lo) / (hi - lo), 0, 1)
    return (inv / rp["factor"] * (1 - e) + inv * e,
            float(rp["attention_factor"]))


def rope(x, inv, scale):
    """x: (B, T, H, hd); pairs (i, i + hd/2) turned by t inv_i, cos and
    sin times ``scale``."""
    T, half = x.shape[1], x.shape[-1] // 2
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(scale * np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(scale * np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention_op(p, z, config, mm, kind, window):
    B, T, _ = z.shape
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    hd = config["head_dim"]
    q = mm("btd,de->bte", z, p["q_w"]).reshape(B, T, H, hd)
    k = mm("btd,de->bte", z, p["k_w"]).reshape(B, T, K, hd)
    v = mm("btd,de->bte", z, p["v_w"]).reshape(B, T, K, hd)
    inv, scale = inv_freq(config, kind)
    q, k = rope(q, inv, scale), rope(k, inv, scale)
    qb = QUERY_BLOCK if T % QUERY_BLOCK == 0 else T

    @jax.checkpoint
    def query_block(i, kh, vh, qh):
        rows = i * qb + jnp.arange(qb)[:, None]
        cols = jnp.arange(T)[None, :]
        keep = cols <= rows
        if window is not None:
            keep = keep & (rows - cols < window)
        s = mm("btd,bsd->bts", lax.dynamic_slice_in_dim(qh, i * qb, qb, 1),
               kh) / np.sqrt(hd)
        a = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), axis=-1)
        return mm("bts,bsd->btd", a, vh)

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args                              # (B, T, hd)
        out = lax.map(lambda i: query_block(i, kh, vh, qh),
                      jnp.arange(T // qb))             # (T/qb, B, qb, hd)
        return jnp.moveaxis(out, 0, 1).reshape(B, T, hd)

    serves = np.arange(H) // (H // K)                  # query head -> KV head
    ctx = lax.map(one_head, (jnp.moveaxis(q, 2, 0),
                             jnp.moveaxis(k, 2, 0)[serves],
                             jnp.moveaxis(v, 2, 0)[serves]))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(B, T, H * hd)
    return mm("bte,ed->btd", ctx, p["o_w"])


def _layer(p, x, kind, window, config, mm):
    eps = config["rms_norm_eps"]
    x = x + attention_op(p, _norm(x, p["op_norm"], eps), config, mm, kind,
                         window)
    y, idx = routed_part(p, _norm(x, p["ffn_norm"], eps), config, mm)
    return x + y, idx


def hidden_states(params, ids, config, precision="float32"):
    """-> (final hidden states after ``out_norm``, the chosen expert ids
    of every layer)."""
    mm = partial(_einsum, precision)
    x = params["wte"][ids]
    chosen = []
    for l, p in enumerate(params["layers"]):
        layer = jax.checkpoint(partial(
            _layer, kind=config["layer_types"][l],
            window=layer_window(config, l), config=config, mm=mm))
        x, idx = layer(p, x)
        chosen.append(idx)
    return _norm(x, params["out_norm"], config["rms_norm_eps"]), chosen


def logits_of(params, ids, config, precision="float32"):
    x, _ = hidden_states(params, ids, config, precision)
    return _einsum(precision, "btd,dv->btv", x, params["head_w"])


def summed_loss(params, ids, labels, config, precision="float32"):
    """Summed next-token cross-entropy of a block of rows."""
    logits = logits_of(params, ids, config, precision)
    lse = jax.nn.logsumexp(logits, axis=-1)
    at = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    return jnp.sum(lse - at)


# ---------------------------------------------------------------------------
# the training step and its evidence
# ---------------------------------------------------------------------------
def evidence(config, traffic, seed, leaf_norms,
             precision="float32", fault=None, rows=1):
    """The reference's evidence for a cell: weights and batches made
    from the seed here, nothing taken from the program."""
    return train_evidence(
        config, traffic, init_params(config, seed),
        make_batches(config, traffic, seed), leaf_norms,
        precision=precision, fault=fault, rows=rows)


def train_evidence(config, traffic, params, batches, leaf_norms,
                   precision="float32", fault=None, rows=1):
    """Runs the first ``check_steps`` training steps from ``params`` on
    ``batches`` and returns the evidence the harness compares
    (``checks/training.py``).  ``fault`` plants one of the faults a
    training cell can have: ``half_batch`` (the second half of every
    batch left out, the mean taken over the rest) or ``state_unchanged``
    (the step returns its state as it got it)."""
    opt = config["assumed"]["optimizer"]
    vg = jax.jit(jax.value_and_grad(partial(
        summed_loss, config=config, precision=precision)))
    add = jax.jit(lambda a, b: jax.tree.map(jnp.add, a, b),
                  donate_argnums=(0,))
    update = jax.jit(lambda p, g, m, v, t, n: adamw(
        p, jax.tree.map(lambda x: x / n, g), m, v, t, opt),
        donate_argnums=(1, 2, 3))
    norms = jax.jit(leaf_norms)
    diff_norms = jax.jit(lambda a, b: leaf_norms(
        jax.tree.map(jnp.subtract, a, b)))
    p0 = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    out = {"loss": []}
    for t in range(1, traffic["check_steps"] + 1):
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
