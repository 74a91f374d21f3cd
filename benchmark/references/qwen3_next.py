"""Plain reference of the Qwen3-Next training step: forward, loss,
gradients and the AdamW update in straightforward ``jax.numpy`` and
float32 at ``highest`` matmul precision — no kernels, no sort, no chunked
scan, no program code.

The equations (config keys in backticks; ``N(x; w) = x rsqrt(mean(x^2) +
rms_norm_eps) (1 + w)``, a zero-centred RMSNorm; no bias anywhere):

- ``x0 = wte[ids]``; layer ``l`` (0-based): ``h = x + Mix_l(N(x;
  op_norm))``, ``x' = h + MoE(N(h; ffn_norm))``; output ``N(x_L;
  out_norm) head_w``; loss = mean next-token cross-entropy, no auxiliary
  loss.  ``Mix_l`` is the gated attention where ``(l + 1) %
  full_attention_interval == 0``, else the gated delta rule.
- Gated attention: ``[q | gate] = z W_q`` per head (``num_attention_heads``
  heads of ``2 head_dim`` columns: q, then the gate), ``k = z W_k``, ``v =
  z W_v`` (``num_key_value_heads`` heads); ``q <- N(q; q_norm)``, ``k <-
  N(k; k_norm)`` over the head size; rotate-half RoPE on the first
  ``partial_rotary_factor head_dim`` components (pairs ``(i, i + r/2)``,
  theta ``rope_theta``); causal ``softmax(q k^T / sqrt(head_dim)) v``, KV
  head j serving query heads ``j g .. j g + g - 1``; ``(ctx *
  sigmoid(gate)) W_o``.
- Gated delta rule: ``[q, k, v, g_z] = z W_qkvz`` (``linear_num_key_heads``
  heads of q, then of k, ``linear_num_value_heads`` heads of v, then of
  g_z), ``[b, a] = z W_ba``; ``c = silu(conv(concat(q, k, v)))`` with
  ``c_t = sum_{j < linear_conv_kernel_dim} u_j * s_{t-j}``, ``s_{<0} = 0``
  (depth-wise, causal within a row); ``beta = sigmoid(b)``, ``g =
  -exp(A_log) softplus(a + dt_bias)``; q and k heads repeated to the v
  heads (k head j serves v heads ``j r .. j r + r - 1``), ``q <- q /
  |q|``, ``k <- k / |k|`` (1e-6 inside the root), ``q <- q /
  sqrt(d_k)``.  Per row and v head, ``S_0 = 0`` and, **token by token**
  (``lax.scan`` over T), ``S <- exp(g_t) S``; ``u_t = beta_t (v_t - S^T
  k_t)``; ``S <- S + k_t u_t^T``; ``o_t = S^T q_t``.  Then per head
  ``gdn_norm * o rsqrt(mean(o^2) + eps) * silu(g_z)`` and ``W_out``.
- MoE: ``p = softmax(z W_r)`` over the router's whole width in float32;
  chosen = top ``num_experts_per_tok`` of p; ``w_e = p_e / sum of the
  chosen p``; the sum of ``w_e (silu(z W1_e) * z W3_e) W2_e`` over the
  chosen experts that are HELD (``num_experts`` of them from
  ``deployment.first_expert`` on; the router's width is
  ``deployment.router_width``) — a loop over the held experts, every one
  on every token, times its routing weight (nought where not chosen) —
  plus the shared expert ``sigmoid(z w_sg) (silu(z W1s) * z W3s) W2s``.

Stated departures and assumptions (the configuration file lists them):
the chip's share of the experts and of the vocabulary; the column order
inside ``W_qkvz`` and ``W_ba``; the initial values; no multi-token
prediction module.

Parameter layout (the program's, so that one set of seeded weights serves
both sides): ``wte`` (V, D), ``layers`` a list of one dict per layer —
``op_norm``, ``ffn_norm`` (D,); attention: ``q_w`` (D, H 2 hd), ``k_w``,
``v_w`` (D, K hd), ``q_norm``, ``k_norm`` (hd,), ``o_w`` (H hd, D); delta
rule: ``qkvz_w`` (D, 2 Hk dk + 2 Hv dv), ``ba_w`` (D, 2 Hv), ``conv_w``
(taps, 2 Hk dk + Hv dv), ``A_log``, ``dt_bias`` (Hv,), ``gdn_norm``
(dv,), ``out_w`` (Hv dv, D); every layer: ``router_w`` (D, E), ``w1``,
``w3`` (held, D, F), ``w2`` (held, F, D), ``shared_w1``, ``shared_w3``
(D, Fs), ``shared_w2`` (Fs, D), ``shared_gate_w`` (D, 1) — then
``out_norm`` (D,), ``head_w`` (D, V).

The batch is walked in blocks of rows, each layer is recomputed in the
backward pass (``jax.checkpoint``), attention runs one query head at a
time, the experts one at a time, and the recurrence is a scan over
segments of a scan over tokens with the inner one recomputed (a flat scan
would keep T states of 2 MB a row and layer; the operations and their
order are the flat scan's), so that float32 at the timed sizes fits one
chip.  ``precision`` selects what the matrix multiplications see (the
router and the recurrence always float32; rounding the recurrence's q, k
and v as well moved the control's readings by under 5 %, my chip runs,
PR 35): ``float32`` (the reference), ``bfloat16``, or ``fp8`` (operands
rounded to e4m3 with one scale per tensor — the control).
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

SEGMENT = 128       # tokens an inner scan of the recurrence walks


def _router(config):
    dep = config.get("deployment", {})
    return (dep.get("router_width", config["num_experts"]),
            dep.get("first_expert", 0))


def is_attention(config, l):
    return (l + 1) % config["full_attention_interval"] == 0


def init_params(config, seed):
    """Seeded float32 weights: normal(0, 0.02), the zero-centred gains 0,
    ``gdn_norm`` 1, ``A_log = log(U(0, 16))`` (drawn from [1e-4, 16) so
    that the logarithm is finite), ``dt_bias`` 1."""
    c = config
    D, V = c["hidden_size"], c["vocab_size"]
    H, K, hd = c["num_attention_heads"], c["num_key_value_heads"], \
        c["head_dim"]
    Hk, Hv = c["linear_num_key_heads"], c["linear_num_value_heads"]
    dk, dv = c["linear_key_head_dim"], c["linear_value_head_dim"]
    F, Fs = c["moe_intermediate_size"], c["shared_expert_intermediate_size"]
    held, taps = c["num_experts"], c["linear_conv_kernel_dim"]
    E, _ = _router(c)
    L = c["num_hidden_layers"]
    conv = 2 * Hk * dk + Hv * dv

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 16 * L + 2))

        def normal(*shape):
            return jax.random.normal(next(keys), shape, jnp.float32) * 0.02

        layers = []
        for l in range(L):
            p = {"op_norm": jnp.zeros((D,)), "ffn_norm": jnp.zeros((D,))}
            if is_attention(c, l):
                p.update(q_w=normal(D, H * 2 * hd), k_w=normal(D, K * hd),
                         v_w=normal(D, K * hd), q_norm=jnp.zeros((hd,)),
                         k_norm=jnp.zeros((hd,)), o_w=normal(H * hd, D))
            else:
                p.update(
                    qkvz_w=normal(D, conv + Hv * dv),
                    ba_w=normal(D, 2 * Hv), conv_w=normal(taps, conv),
                    A_log=jnp.log(jax.random.uniform(
                        next(keys), (Hv,), jnp.float32, 1e-4, 16.0)),
                    dt_bias=jnp.ones((Hv,)), gdn_norm=jnp.ones((dv,)),
                    out_w=normal(Hv * dv, D))
            p.update(router_w=normal(D, E), w1=normal(held, D, F),
                     w3=normal(held, D, F), w2=normal(held, F, D),
                     shared_w1=normal(D, Fs), shared_w3=normal(D, Fs),
                     shared_w2=normal(Fs, D), shared_gate_w=normal(D, 1))
            layers.append(p)
        return {"wte": normal(V, D), "layers": layers,
                "out_norm": jnp.zeros((D,)), "head_w": normal(D, V)}

    return make(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _norm(x, w, eps):
    return x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * (1 + w)


def partial_rope(x, theta, rotary):
    """x: (B, T, H, hd); of the first ``rotary`` components the pairs
    (i, i + rotary/2) are rotated by t theta^(-2i/rotary)."""
    T = x.shape[1]
    inv = theta ** (-np.arange(0, rotary, 2, dtype=np.float64) / rotary)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    x1, x2 = x[..., :rotary // 2], x[..., rotary // 2:rotary]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin,
                            x[..., rotary:]], -1)


def attention_op(p, z, config, mm):
    B, T, _ = z.shape
    H, K = config["num_attention_heads"], config["num_key_value_heads"]
    hd, eps = config["head_dim"], config["rms_norm_eps"]
    theta = float(config["rope_theta"])
    rotary = int(config["partial_rotary_factor"] * hd)
    qg = mm("btd,de->bte", z, p["q_w"]).reshape(B, T, H, 2, hd)
    q, gate = qg[..., 0, :], qg[..., 1, :]
    k = mm("btd,de->bte", z, p["k_w"]).reshape(B, T, K, hd)
    v = mm("btd,de->bte", z, p["v_w"]).reshape(B, T, K, hd)
    q = partial_rope(_norm(q, p["q_norm"], eps), theta, rotary)
    k = partial_rope(_norm(k, p["k_norm"], eps), theta, rotary)
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
    ctx = jnp.moveaxis(ctx, 0, 2) * jax.nn.sigmoid(gate)
    return mm("bte,ed->btd", ctx.reshape(B, T, H * hd), p["o_w"])


def delta_rule(q, k, v, g, beta):
    """The recurrence token by token.  q, k: (B, T, H, dk); v: (B, T, H,
    dv); g, beta: (B, T, H).  -> o (B, T, H, dv).  Elementwise products
    and sums only: float32 whatever the device's matmul precision."""
    B, T, H, dk = q.shape

    def token(S, x):
        q_t, k_t, v_t, g_t, b_t = x                    # (B, H, d), (B, H)
        S = jnp.exp(g_t)[..., None, None] * S
        u = b_t[..., None] * (v_t - jnp.sum(S * k_t[..., :, None], -2))
        S = S + k_t[..., :, None] * u[..., None, :]
        return S, jnp.sum(S * q_t[..., :, None], -2)

    seg = SEGMENT if T % SEGMENT == 0 else T

    @jax.checkpoint
    def segment(S, xs):
        return lax.scan(token, S, xs)

    xs = tuple(jnp.moveaxis(a, 1, 0).reshape(T // seg, seg, *a.shape[:1],
                                             *a.shape[2:])
               for a in (q, k, v, g, beta))
    S0 = jnp.zeros((B, H, dk, v.shape[-1]), jnp.float32)
    _, o = lax.scan(segment, S0, xs)
    return jnp.moveaxis(o.reshape(T, B, H, -1), 0, 1)


def delta_op(p, z, config, mm):
    B, T, _ = z.shape
    Hk, Hv = config["linear_num_key_heads"], config["linear_num_value_heads"]
    dk, dv = config["linear_key_head_dim"], config["linear_value_head_dim"]
    n_qk, n_v = Hk * dk, Hv * dv
    mixed = mm("btd,de->bte", z, p["qkvz_w"])
    ba = mm("btd,de->bte", z, p["ba_w"])
    s = mixed[..., :2 * n_qk + n_v]
    c = jnp.zeros_like(s)
    for j in range(p["conv_w"].shape[0]):
        c = c + p["conv_w"][j] * jnp.pad(s, ((0, 0), (j, 0), (0, 0)))[:, :T]
    c = jax.nn.silu(c)
    q = c[..., :n_qk].reshape(B, T, Hk, dk)
    k = c[..., n_qk:2 * n_qk].reshape(B, T, Hk, dk)
    v = c[..., 2 * n_qk:].reshape(B, T, Hv, dv)
    beta = jax.nn.sigmoid(ba[..., :Hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., Hv:] + p["dt_bias"])
    q = jnp.repeat(q, Hv // Hk, axis=2)
    k = jnp.repeat(k, Hv // Hk, axis=2)
    q = q * lax.rsqrt(jnp.sum(q * q, -1, keepdims=True) + 1e-6) / np.sqrt(dk)
    k = k * lax.rsqrt(jnp.sum(k * k, -1, keepdims=True) + 1e-6)
    o = delta_rule(q, k, v, g, beta)
    y = o * lax.rsqrt(jnp.mean(o * o, -1, keepdims=True)
                      + config["rms_norm_eps"])
    gz = mixed[..., 2 * n_qk + n_v:].reshape(B, T, Hv, dv)
    y = p["gdn_norm"] * y * jax.nn.silu(gz)
    return mm("bte,ed->btd", y.reshape(B, T, n_v), p["out_w"])


def route(p, z, config):
    """-> (chosen expert ids (B, T, k), their weights (B, T, k)); always
    float32 at ``highest``."""
    probs = jax.nn.softmax(jnp.einsum("btd,de->bte", z, p["router_w"],
                                      precision=HIGHEST), axis=-1)
    w, idx = lax.top_k(probs, config["num_experts_per_tok"])
    if config.get("norm_topk_prob", True):
        w = w / jnp.sum(w, -1, keepdims=True)
    return idx, w


def swiglu(z, w1, w3, w2, mm):
    h = jax.nn.silu(mm("btd,df->btf", z, w1)) * mm("btd,df->btf", z, w3)
    return mm("btf,fd->btd", h, w2)


def routed_part(p, z, config, mm, first=None):
    """The held experts' part of the layer: a loop over them, every one
    on every token, times its routing weight.  -> (y, chosen ids)"""
    if first is None:
        _, first = _router(config)
    idx, w = route(p, z, config)

    @jax.checkpoint
    def one_expert(y, x):
        e, w1, w3, w2 = x
        w_e = jnp.sum(jnp.where(idx == first + e, w, 0.0), axis=-1)
        return y + w_e[..., None] * swiglu(z, w1, w3, w2, mm), None

    held = p["w1"].shape[0]
    y, _ = lax.scan(one_expert, jnp.zeros_like(z),
                    (jnp.arange(held), p["w1"], p["w3"], p["w2"]))
    return y, idx


def shared_part(p, z, mm):
    gate = jax.nn.sigmoid(mm("btd,de->bte", z, p["shared_gate_w"]))
    return gate * swiglu(z, p["shared_w1"], p["shared_w3"], p["shared_w2"],
                         mm)


def _layer(p, x, attention, config, mm):
    eps = config["rms_norm_eps"]
    z = _norm(x, p["op_norm"], eps)
    x = x + (attention_op(p, z, config, mm) if attention
             else delta_op(p, z, config, mm))
    z = _norm(x, p["ffn_norm"], eps)
    y, idx = routed_part(p, z, config, mm)
    return x + y + shared_part(p, z, mm), idx


def hidden_states(params, ids, config, precision="float32"):
    """-> (final hidden states after ``out_norm``, the chosen expert ids
    of every layer)."""
    mm = partial(_einsum, precision)
    x = params["wte"][ids]
    chosen = []
    for l, p in enumerate(params["layers"]):
        layer = jax.checkpoint(partial(
            _layer, attention=is_attention(config, l), config=config,
            mm=mm))
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
def adamw(params, grads, m, v, t, o):
    """Decoupled AdamW over every leaf (none is frozen)."""
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
