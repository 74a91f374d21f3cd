"""Plain reference of the LFM2-MoE training step: forward, loss, gradients
and the AdamW update in straightforward ``jax.numpy`` and float32 at
``highest`` matmul precision — no kernels, no sort, no program code.

The equations (config keys in backticks; ``RMS(x; g) = x rsqrt(mean(x^2)
+ norm_eps) g``; no bias anywhere):

- ``x0 = wte[ids]``; layer ``l``: ``h = x + Op_l(RMS(x; op_norm))``,
  ``x' = h + FFN_l(RMS(h; ffn_norm))``; output ``RMS(x_L; out_norm)
  head_w``; loss = mean next-token cross-entropy, no auxiliary loss.
- ``Op`` for ``layer_types[l] == "conv"``: ``[b, c, u] = split3(z
  W_in)``, ``s = b * u``, ``y_t = sum_{j < conv_L_cache} w_j * s_{t-j}``
  (``s_{<0} = 0``: depth-wise, causal within a row), ``(c * y) W_out``.
- ``Op`` for ``"full_attention"``: ``q = z W_q`` (``num_attention_heads``
  heads), ``k = z W_k``, ``v = z W_v`` (``num_key_value_heads`` heads);
  q and k through RMS over the head size with one gain shared by the
  heads; rotate-half RoPE over the whole head, theta ``rope_theta``;
  causal ``softmax(q k^T / sqrt(head size)) v``, KV head j serving query
  heads ``j g .. j g + g - 1``; ``ctx W_o``.
- ``FFN`` for ``l < num_dense_layers``: ``(silu(z W1) * z W3) W2``.
- ``FFN`` otherwise: ``s = sigmoid(z W_g)`` over the router's whole width
  in float32; chosen = top ``num_experts_per_tok`` of ``s + bias`` (the
  bias takes no gradient and no update); ``w_e = s_e / (sum of the chosen
  s + 1e-6) * routed_scaling_factor``; the sum of ``w_e (silu(z W1_e) * z
  W3_e) W2_e`` over the chosen experts that are HELD (``num_experts`` of
  them from ``deployment.first_expert`` on; the router's width is
  ``deployment.router_width``).  Computed here in the dense form: every
  held expert on every token, times its routing weight (nought where the
  expert was not chosen).

Stated departures and assumptions (the configuration file lists them):
the chip's share of the experts and of the vocabulary; a separate head
matrix; the q/k gains, the 1e-6 and the selection bias drawn from the
seed are the family's convention, not in the published config.

Parameter layout (the program's, so that one set of seeded weights serves
both sides): ``wte`` (V, D), ``layers`` a list of one dict per layer —
``op_norm``, ``ffn_norm`` (D,); conv: ``conv_in_w`` (D, 3, D) (section s
is columns ``s D ..`` of W_in), ``conv_w`` (taps, D), ``conv_out_w`` (D,
D); attention: ``q_w`` (D, H hd), ``k_w``, ``v_w`` (D, K hd), ``q_norm``,
``k_norm`` (hd,), ``o_w`` (H hd, D); dense FFN: ``w1``, ``w3`` (D, F),
``w2`` (F, D); experts: ``router_w`` (D, E), ``router_bias`` (E,),
``w1``, ``w3`` (held, D, Fm), ``w2`` (held, Fm, D) — then ``out_norm``
(D,), ``head_w`` (D, V).

The batch is walked in blocks of rows, each layer is recomputed in the
backward pass (``jax.checkpoint``) and attention runs one query head at a
time, so that float32 at the timed sizes fits one chip.  ``precision``
selects what the matrix multiplications see (the router always float32):
``float32`` (the reference), ``bfloat16``, or ``fp8`` (operands rounded
to e4m3 with one scale per tensor — the control).
"""
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# one definition of the precisions (float32 at ``highest``, bfloat16, the
# fp8 control) and of the token pool for every family: ids uniform over
# the vocabulary held, labels the next token of the same fixed-size row
from benchmark.references.gpt import (  # noqa: F401
    HIGHEST, _einsum, make_batches)

FROZEN = ("router_bias",)       # no gradient, no AdamW update


def _router(config):
    dep = config.get("deployment", {})
    return (dep.get("router_width", config["num_experts"]),
            dep.get("first_expert", 0))


def init_params(config, seed):
    """Seeded float32 weights: normal(0, 0.02), gains 1, the selection
    bias normal(0, 0.01)."""
    D, V = config["hidden_size"], config["vocab_size"]
    hd = D // config["num_attention_heads"]
    kv = config["num_key_value_heads"] * hd
    F, Fm = config["intermediate_size"], config["moe_intermediate_size"]
    held, taps = config["num_experts"], config["conv_L_cache"]
    E, _ = _router(config)
    kinds = tuple(config["layer_types"])
    n_dense = config["num_dense_layers"]

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 8 * len(kinds) + 2))

        def normal(*shape, std=0.02):
            return jax.random.normal(next(keys), shape, jnp.float32) * std

        layers = []
        for l, kind in enumerate(kinds):
            p = {"op_norm": jnp.ones((D,)), "ffn_norm": jnp.ones((D,))}
            if kind == "conv":
                p.update(conv_in_w=normal(D, 3, D), conv_w=normal(taps, D),
                         conv_out_w=normal(D, D))
            else:
                p.update(q_w=normal(D, D), k_w=normal(D, kv),
                         v_w=normal(D, kv), q_norm=jnp.ones((hd,)),
                         k_norm=jnp.ones((hd,)), o_w=normal(D, D))
            if l < n_dense:
                p.update(w1=normal(D, F), w3=normal(D, F), w2=normal(F, D))
            else:
                p.update(router_w=normal(D, E),
                         router_bias=normal(E, std=0.01),
                         w1=normal(held, D, Fm), w3=normal(held, D, Fm),
                         w2=normal(held, Fm, D))
            layers.append(p)
        return {"wte": normal(V, D), "layers": layers,
                "out_norm": jnp.ones((D,)), "head_w": normal(D, V)}

    return make(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _rms(x, g, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def _rope(x, theta):
    """x: (B, T, H, hd); pairs (i, i + hd/2) rotated by t theta^(-2i/hd)."""
    T, hd = x.shape[1], x.shape[-1]
    inv = theta ** (-np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _conv_op(p, z, mm):
    D = z.shape[-1]
    proj = mm("btd,de->bte", z, p["conv_in_w"].reshape(D, 3 * D))
    b, c, u = jnp.split(proj, 3, axis=-1)
    s = b * u
    y = jnp.zeros_like(s)
    for j in range(p["conv_w"].shape[0]):
        shifted = jnp.pad(s, ((0, 0), (j, 0), (0, 0)))[:, :s.shape[1]]
        y = y + p["conv_w"][j] * shifted
    return mm("btd,de->bte", c * y, p["conv_out_w"])


def _attention_op(p, z, config, mm):
    B, T, D = z.shape
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = D // H, config["norm_eps"]
    theta = config["rope_parameters"]["rope_theta"]
    q = mm("btd,de->bte", z, p["q_w"]).reshape(B, T, H, hd)
    k = mm("btd,de->bte", z, p["k_w"]).reshape(B, T, K, hd)
    v = mm("btd,de->bte", z, p["v_w"]).reshape(B, T, K, hd)
    q = _rope(_rms(q, p["q_norm"], eps), theta)
    k = _rope(_rms(k, p["k_norm"], eps), theta)
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args                              # (B, T, hd)
        s = mm("btd,bsd->bts", qh, kh) / np.sqrt(hd)
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return mm("bts,bsd->btd", a, vh)

    serves = np.arange(H) // (H // K)                  # query head -> KV head
    ctx = lax.map(one_head, (jnp.moveaxis(q, 2, 0),
                             jnp.moveaxis(k, 2, 0)[serves],
                             jnp.moveaxis(v, 2, 0)[serves]))
    ctx = jnp.moveaxis(ctx, 0, 2).reshape(B, T, D)
    return mm("btd,de->bte", ctx, p["o_w"])


def _dense_ffn(p, z, mm):
    h = jax.nn.silu(mm("btd,df->btf", z, p["w1"])) \
        * mm("btd,df->btf", z, p["w3"])
    return mm("btf,fd->btd", h, p["w2"])


def route(p, z, config):
    """-> (chosen expert ids (B, T, k), their weights (B, T, k)); always
    float32 at ``highest``."""
    s = jax.nn.sigmoid(jnp.einsum("btd,de->bte", z, p["router_w"],
                                  precision=HIGHEST))
    _, idx = lax.top_k(s + lax.stop_gradient(p["router_bias"]),
                       config["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-6)
    return idx, w * config["routed_scaling_factor"]


def _experts_ffn(p, z, config, mm):
    _, first = _router(config)
    idx, w = route(p, z, config)
    y = jnp.zeros_like(z)
    for e in range(p["w1"].shape[0]):
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        h = jax.nn.silu(mm("btd,df->btf", z, p["w1"][e])) \
            * mm("btd,df->btf", z, p["w3"][e])
        y = y + w_e[..., None] * mm("btf,fd->btd", h, p["w2"][e])
    return y, idx


def _layer(p, x, kind, dense, config, mm):
    eps = config["norm_eps"]
    z = _rms(x, p["op_norm"], eps)
    x = x + (_conv_op(p, z, mm) if kind == "conv"
             else _attention_op(p, z, config, mm))
    z = _rms(x, p["ffn_norm"], eps)
    if dense:
        return x + _dense_ffn(p, z, mm), None
    y, idx = _experts_ffn(p, z, config, mm)
    return x + y, idx


def hidden_states(params, ids, config, precision="float32"):
    """-> (final hidden states after ``out_norm``, the chosen expert ids
    of every expert layer)."""
    mm = partial(_einsum, precision)
    x = params["wte"][ids]
    chosen = []
    for l, (kind, p) in enumerate(zip(config["layer_types"],
                                      params["layers"])):
        layer = jax.checkpoint(partial(
            _layer, kind=kind, dense=l < config["num_dense_layers"],
            config=config, mm=mm))
        x, idx = layer(p, x)
        if idx is not None:
            chosen.append(idx)
    return _rms(x, params["out_norm"], config["norm_eps"]), chosen


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
def _is_frozen(path):
    return getattr(path[-1], "key", None) in FROZEN


def adamw(params, grads, m, v, t, o):
    b1, b2 = o["beta1"], o["beta2"]
    lr, wd, eps = o["learning_rate"], o["weight_decay"], o["eps"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t
    params = jax.tree_util.tree_map_with_path(
        lambda path, p, m_, v_: p if _is_frozen(path)
        else (1 - lr * wd) * p - lr * (m_ / c1) / (jnp.sqrt(v_ / c2) + eps),
        params, m, v)
    return params, m, v


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
    steps = traffic["check_steps"]
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
