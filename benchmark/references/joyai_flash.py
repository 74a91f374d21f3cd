"""Plain reference of the JoyAI-LLM-Flash training step: forward, the
two-term loss, gradients and the AdamW update in straightforward
``jax.numpy`` and float32 at ``highest`` matmul precision — no kernels,
no sort, no program code.

The equations (config keys in backticks; ``N(x; w) = w x rsqrt(mean(x^2)
+ rms_norm_eps)``, a plain RMSNorm; no bias but the router's selection
bias; every key is one of the DeepSeek-V3 family's):

- ``x0 = wte[ids]``; layer ``l``: ``h = x + MLA(N(x; op_norm))``, ``x' =
  h + FFN_l(N(h; ffn_norm))``; logits ``N(x_L; out_norm) head_w``.
- MLA: ``c_q = N(z W_qa; q_a_norm)`` (``q_lora_rank``), ``q = c_q W_qb``,
  per head ``[q_n (qk_nope_head_dim) | q_r (qk_rope_head_dim)]``; ``[c |
  k_r] = z W_kva`` (``kv_lora_rank`` + ``qk_rope_head_dim``), ``c_kv =
  N(c; kv_a_norm)``, ``c_kv W_kvb`` per head ``[k_n | v (v_head_dim)]``.
  RoPE on ``q_r`` of every head and on the one ``k_r`` all heads share,
  in interleaved pairing: components ``(2i, 2i + 1)`` are one complex
  number turned by ``t rope_theta^(-2i / qk_rope_head_dim)``.  ``q_h =
  [q_n | R(q_r)]``, ``k_h = [k_n | R(k_r)]``; causal ``softmax(q k^T /
  sqrt(qk_nope_head_dim + qk_rope_head_dim)) v``; ``ctx W_o``.  One head
  at a time: the (T, T) scores of a head and row are 268 MB in float32.
- ``FFN_l`` for ``l < first_k_dense_replace``: ``(silu(z W1) * z W3) W2``
  at ``intermediate_size``.  Otherwise ``s = sigmoid(z W_r)`` over the
  router's whole width in float32; chosen = top ``num_experts_per_tok``
  of ``s + b`` (``noaux_tc`` with ``n_group = topk_group = 1``; ``b``
  takes no gradient and no update); ``w_e = s_e / (sum of the chosen s +
  1e-20) * routed_scaling_factor``; the sum of ``w_e (silu(z W1_e) * z
  W3_e) W2_e`` over the chosen experts that are HELD (``n_routed_experts``
  of them from ``deployment.first_expert`` on; the router's width is
  ``deployment.router_width``) — a loop over the held experts, every one
  on every token, times its routing weight (nought where not chosen) —
  plus the shared expert ``(silu(z W1s) * z W3s) W2s``, no gate.
- The MTP module (``num_nextn_predict_layers`` 1): with ``h_i`` the
  trunk's state after its last block, before ``out_norm``, and ``t_{i+1}
  = labels_i``: ``u_i = concat(N(h_i; h_norm), N(wte[t_{i+1}]; e_norm))
  eh_proj``; one expert layer of its own on the row ``u``; logits ``N(.;
  mtp out_norm) head_w`` with the shared ``wte`` and ``head_w``; target
  ``labels_{i+1}``, the last position of a row has none.
- ``loss = mean_i CE(main_i, labels_i) + lambda * sum_{i < T-1} CE(mtp_i,
  labels_{i+1}) / (B (T - 1))``, ``lambda = assumed.mtp_loss_weight``.

Stated departures and assumptions (the configuration file lists them):
the chip's share of the experts and of the vocabulary; lambda; ``h`` taken
before the final norm and the order of ``eh_proj``'s two halves; the
selection bias drawn from the seed.

Parameter layout (the program's, so that one set of seeded weights serves
both sides): ``wte`` (V, D); ``layers``, one dict a layer — ``op_norm``,
``ffn_norm`` (D,), ``q_a_w`` (D, rq), ``q_a_norm`` (rq,), ``q_b_w`` (rq, H
(dn + dr)), ``kv_a_w`` (D, rkv + dr), ``kv_a_norm`` (rkv,), ``kv_b_w``
(rkv, H (dn + dv)), ``o_w`` (H dv, D); dense: ``w1``, ``w3`` (D, F),
``w2`` (F, D); experts: ``router_w`` (D, E), ``router_bias`` (E,), ``w1``,
``w3`` (held, D, Fm), ``w2`` (held, Fm, D), ``shared_w1``, ``shared_w3``
(D, Fs), ``shared_w2`` (Fs, D) —; ``out_norm`` (D,), ``head_w`` (D, V);
``mtp``: ``h_norm``, ``e_norm`` (D,), ``eh_proj`` (2 D, D), ``layer`` (an
expert layer's dict), ``out_norm`` (D,).

The batch is walked in blocks of rows, each layer is recomputed in the
backward pass (``jax.checkpoint``), attention runs one head at a time and
the experts one at a time, so that float32 at the timed sizes fits one
chip.  ``precision`` selects what the matrix multiplications see (the
router always float32): ``float32`` (the reference), ``bfloat16``, or
``fp8`` (operands rounded to e4m3 with one scale per tensor — the
control).
"""
from functools import partial

import numpy as np

import jax
import jax.numpy as jnp
from jax import lax

# one definition of the precisions (float32 at ``highest``, bfloat16, the
# fp8 control) and of the token pool for every family; AdamW with the
# selection bias frozen is LFM2's
from benchmark.references.gpt import (  # noqa: F401
    HIGHEST, _einsum, make_batches)
from benchmark.references.lfm2_moe import adamw  # noqa: F401


def _router(config):
    dep = config.get("deployment", {})
    return (dep.get("router_width", config["n_routed_experts"]),
            dep.get("first_expert", 0))


def init_params(config, seed):
    """Seeded float32 weights: normal(0, 0.02), gains 1, the selection
    bias normal(0, 0.01)."""
    c = config
    D, V, H = c["hidden_size"], c["vocab_size"], c["num_attention_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    F, Fm, held = c["intermediate_size"], c["moe_intermediate_size"], \
        c["n_routed_experts"]
    Fs = c["n_shared_experts"] * Fm
    E, _ = _router(c)
    L, dense = c["num_hidden_layers"], c["first_k_dense_replace"]

    @jax.jit
    def make(key):
        keys = iter(jax.random.split(key, 16 * (L + 1) + 4))

        def normal(*shape, std=0.02):
            return jax.random.normal(next(keys), shape, jnp.float32) * std

        def layer(is_dense):
            p = {"op_norm": jnp.ones((D,)), "ffn_norm": jnp.ones((D,)),
                 "q_a_w": normal(D, rq), "q_a_norm": jnp.ones((rq,)),
                 "q_b_w": normal(rq, H * (dn + dr)),
                 "kv_a_w": normal(D, rkv + dr),
                 "kv_a_norm": jnp.ones((rkv,)),
                 "kv_b_w": normal(rkv, H * (dn + dv)),
                 "o_w": normal(H * dv, D)}
            if is_dense:
                p.update(w1=normal(D, F), w3=normal(D, F), w2=normal(F, D))
            else:
                p.update(router_w=normal(D, E),
                         router_bias=normal(E, std=0.01),
                         w1=normal(held, D, Fm), w3=normal(held, D, Fm),
                         w2=normal(held, Fm, D), shared_w1=normal(D, Fs),
                         shared_w3=normal(D, Fs), shared_w2=normal(Fs, D))
            return p

        params = {"wte": normal(V, D),
                  "layers": [layer(l < dense) for l in range(L)],
                  "out_norm": jnp.ones((D,)), "head_w": normal(D, V)}
        if c["num_nextn_predict_layers"]:
            params["mtp"] = {
                "h_norm": jnp.ones((D,)), "e_norm": jnp.ones((D,)),
                "eh_proj": normal(2 * D, D), "layer": layer(False),
                "out_norm": jnp.ones((D,))}
        return params

    return make(jax.random.PRNGKey(seed))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _norm(x, w, eps):
    return w * x * lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps)


def rope_interleaved(x, theta):
    """x: (B, T, H, r): components (2i, 2i + 1) as the complex number
    ``x_2i + i x_2i+1``, turned by ``t theta^(-2i / r)``."""
    B, T, H, r = x.shape
    inv = theta ** (-np.arange(0, r, 2, dtype=np.float64) / r)
    ang = np.arange(T, dtype=np.float64)[:, None] * inv[None, :]
    cos = jnp.asarray(np.cos(ang), jnp.float32)[None, :, None, :]
    sin = jnp.asarray(np.sin(ang), jnp.float32)[None, :, None, :]
    pairs = x.reshape(B, T, H, r // 2, 2)
    re, im = pairs[..., 0], pairs[..., 1]
    return jnp.stack([re * cos - im * sin, im * cos + re * sin],
                     axis=-1).reshape(B, T, H, r)


def mla_op(p, z, config, mm):
    B, T, _ = z.shape
    c = config
    H, eps, theta = c["num_attention_heads"], c["rms_norm_eps"], \
        float(c["rope_theta"])
    rkv = c["kv_lora_rank"]
    dn, dr, dv = c["qk_nope_head_dim"], c["qk_rope_head_dim"], \
        c["v_head_dim"]
    c_q = _norm(mm("btd,de->bte", z, p["q_a_w"]), p["q_a_norm"], eps)
    q = mm("bte,ef->btf", c_q, p["q_b_w"]).reshape(B, T, H, dn + dr)
    kv_a = mm("btd,de->bte", z, p["kv_a_w"])
    c_kv = _norm(kv_a[..., :rkv], p["kv_a_norm"], eps)
    kv = mm("bte,ef->btf", c_kv, p["kv_b_w"]).reshape(B, T, H, dn + dv)
    k_r = rope_interleaved(kv_a[..., None, rkv:], theta)   # (B, T, 1, dr)
    q = jnp.concatenate(
        [q[..., :dn], rope_interleaved(q[..., dn:], theta)], -1)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(k_r, (B, T, H, dr))], -1)
    v = kv[..., dn:]
    mask = jnp.tril(jnp.ones((T, T), bool))

    @jax.checkpoint
    def one_head(args):
        qh, kh, vh = args                              # (B, T, d)
        s = mm("btd,bsd->bts", qh, kh) / np.sqrt(dn + dr)
        a = jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)
        return mm("bts,bsd->btd", a, vh)

    ctx = lax.map(one_head, tuple(jnp.moveaxis(a, 2, 0) for a in (q, k, v)))
    return mm("bte,ed->btd", jnp.moveaxis(ctx, 0, 2).reshape(B, T, H * dv),
              p["o_w"])


def route(p, z, config):
    """-> (chosen expert ids (B, T, k), their weights (B, T, k)); always
    float32 at ``highest``."""
    s = jax.nn.sigmoid(jnp.einsum("btd,de->bte", z, p["router_w"],
                                  precision=HIGHEST))
    _, idx = lax.top_k(s + lax.stop_gradient(p["router_bias"]),
                       config["num_experts_per_tok"])
    chosen = jnp.take_along_axis(s, idx, axis=-1)
    w = chosen / (jnp.sum(chosen, -1, keepdims=True) + 1e-20)
    return idx, w * config["routed_scaling_factor"]


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
    return swiglu(z, p["shared_w1"], p["shared_w3"], p["shared_w2"], mm)


def _layer(p, x, dense, config, mm):
    eps = config["rms_norm_eps"]
    x = x + mla_op(p, _norm(x, p["op_norm"], eps), config, mm)
    z = _norm(x, p["ffn_norm"], eps)
    if dense:
        return x + swiglu(z, p["w1"], p["w3"], p["w2"], mm), None
    y, idx = routed_part(p, z, config, mm)
    return x + y + shared_part(p, z, mm), idx


def hidden_states(params, ids, labels, config, precision="float32"):
    """-> (the trunk's final hidden states after ``out_norm``, the MTP
    module's after its own norm or None, the chosen expert ids of every
    expert layer, the module's last)."""
    mm = partial(_einsum, precision)
    eps = config["rms_norm_eps"]
    x = params["wte"][ids]
    chosen = []
    for l, p in enumerate(params["layers"]):
        x, idx = jax.checkpoint(partial(
            _layer, dense=l < config["first_k_dense_replace"],
            config=config, mm=mm))(p, x)
        if idx is not None:
            chosen.append(idx)
    out, ahead = _norm(x, params["out_norm"], eps), None
    if config["num_nextn_predict_layers"]:
        m = params["mtp"]
        u = mm("bte,ed->btd", jnp.concatenate(
            [_norm(x, m["h_norm"], eps),
             _norm(params["wte"][labels], m["e_norm"], eps)], -1),
            m["eh_proj"])
        h, idx = jax.checkpoint(partial(
            _layer, dense=False, config=config, mm=mm))(m["layer"], u)
        chosen.append(idx)
        ahead = _norm(h, m["out_norm"], eps)
    return out, ahead, chosen


def _summed_ce(x, head_w, labels, precision):
    """Per-position cross-entropy of ``x head_w`` against ``labels``."""
    logits = _einsum(precision, "btd,dv->btv", x, head_w)
    lse = jax.nn.logsumexp(logits, axis=-1)
    return lse - jnp.take_along_axis(logits, labels[..., None], -1)[..., 0]


def loss_terms(params, ids, labels, config, precision="float32"):
    """-> (summed main cross-entropy of a block of rows, summed MTP
    cross-entropy over the positions that have a target two tokens on;
    0 without the module)."""
    out, ahead, _ = hidden_states(params, ids, labels, config, precision)
    main = jnp.sum(_summed_ce(out, params["head_w"], labels, precision))
    if ahead is None:
        return main, jnp.zeros(())
    ce = _summed_ce(ahead[:, :-1], params["head_w"], labels[:, 1:],
                    precision)
    return main, jnp.sum(ce)


def summed_loss(params, ids, labels, config, precision="float32"):
    """The loss of a block of rows times its tokens: ``sum CE_main +
    lambda T / (T - 1) sum CE_mtp``, so that the blocks' sum over ``B T``
    is ``mean CE_main + lambda sum CE_mtp / (B (T - 1))``."""
    main, ahead = loss_terms(params, ids, labels, config, precision)
    T = ids.shape[1]
    lam = config["assumed"]["mtp_loss_weight"]
    return main + lam * T / (T - 1) * ahead


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
