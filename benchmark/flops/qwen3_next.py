"""Operations and bytes of a Qwen3-Next training step, from the
configuration's shapes alone.  The work, not an implementation: forward
and backward as the mathematics requires them, a multiply-add counted as
2, nothing recomputed counted, no padded row counted.  Keys are the
published ones; ``num_experts`` is the number of experts HELD and
``deployment.router_width`` the router's width (the configuration file
says so); the traffic gives ``batch`` and ``seq_len``.

The experts are counted at the expectation under a uniform router —
``tokens x num_experts_per_tok x held / router_width`` assignments a
layer — and not at what a run's router chose: the count must not move
with the seed (the cell's counters give a run's real load).  The gated
delta rule is counted by the recurrence: per token and value head the
read ``S^T k``, the write ``k u^T`` and the read ``S^T q``, ``6 d_k d_v``
operations forward and twice that backward — whatever form (token by
token, chunked, a kernel) computes it.
"""


def _dims(config, traffic):
    c = config
    L, every = c["num_hidden_layers"], c["full_attention_interval"]
    n_attn = sum((l + 1) % every == 0 for l in range(L))
    return {
        "D": c["hidden_size"], "V": c["vocab_size"], "L": L,
        "n_attn": n_attn, "n_gdn": L - n_attn,
        "q": c["num_attention_heads"] * c["head_dim"],
        "kv": c["num_key_value_heads"] * c["head_dim"],
        "Hk": c["linear_num_key_heads"], "Hv": c["linear_num_value_heads"],
        "dk": c["linear_key_head_dim"], "dv": c["linear_value_head_dim"],
        "taps": c["linear_conv_kernel_dim"],
        "F": c["moe_intermediate_size"],
        "Fs": c["shared_expert_intermediate_size"],
        "held": c["num_experts"],
        "width": c.get("deployment", {}).get("router_width",
                                             c["num_experts"]),
        "k": c["num_experts_per_tok"],
        "B": traffic["batch"], "T": traffic["seq_len"]}


def expected_assignments(config, traffic):
    """Assignments to the held experts of ONE layer in one step, under a
    uniform router."""
    d = _dims(config, traffic)
    return d["B"] * d["T"] * d["k"] * d["held"] / d["width"]


def dense_matmul_params(config):
    """Parameters every token multiplies: the mixers' projections (the
    4-tap convolution with them, a multiply-add a tap and channel), the
    routers, the shared experts with their gates and the output head (the
    embedding is a gather)."""
    d = _dims(config, {"batch": 0, "seq_len": 0})
    D = d["D"]
    qk, v = d["Hk"] * d["dk"], d["Hv"] * d["dv"]
    gdn = D * (2 * qk + 2 * v) + D * 2 * d["Hv"] + v * D \
        + d["taps"] * (2 * qk + v)
    attn = D * 2 * d["q"] + 2 * D * d["kv"] + d["q"] * D
    ffn = D * d["width"] + 3 * D * d["Fs"] + D
    return d["n_gdn"] * gdn + d["n_attn"] * attn + d["L"] * ffn + D * d["V"]


def expert_flops(config, traffic):
    """The routed experts of one step, forward and backward: three
    (D, F) matrices an assignment, 2 operations a multiply-add, x 3."""
    d = _dims(config, traffic)
    return (6 * 3 * d["D"] * d["F"] * expected_assignments(config, traffic)
            * d["L"])


def attention_flops(config, traffic):
    """Causal attention of one step over the attention layers: QK^T and
    PV forward (2 * 2*T*T*(heads x head size) a sequence, halved by the
    mask), twice that backward."""
    d = _dims(config, traffic)
    return 6 * d["n_attn"] * d["T"] * d["q"] * (d["B"] * d["T"])


def delta_rule_flops(config, traffic):
    """The recurrence of one step over the delta-rule layers: 6 d_k d_v
    a token and value head forward, twice that backward."""
    d = _dims(config, traffic)
    return (3 * 6 * d["dk"] * d["dv"] * d["Hv"] * d["B"] * d["T"]
            * d["n_gdn"])


def step_flops(config, traffic):
    """Model operations of one training step (forward + backward)."""
    d = _dims(config, traffic)
    return (6 * dense_matmul_params(config) * d["B"] * d["T"]
            + expert_flops(config, traffic)
            + attention_flops(config, traffic)
            + delta_rule_flops(config, traffic))


def samples_per_step(config, traffic):
    return traffic["batch"]


def kernel_work(config, traffic, work, bytes_per_el=2):
    """(operations, bytes) of one step of the named piece of work, over
    all layers that have it, operands in the compute type."""
    d = _dims(config, traffic)
    N, D = d["B"] * d["T"], d["D"]
    if work == "gdn_scan":
        # forward reads q, k (the key heads, once), v and the float32 g
        # and beta and writes o; backward reads q, k, v and do and writes
        # dq, dk, dv, dg, dbeta.  The state never needs to leave the
        # chip's fast memory
        qk, v = d["Hk"] * d["dk"], d["Hv"] * d["dv"]
        return (delta_rule_flops(config, traffic),
                d["n_gdn"] * N * ((6 * qk + 5 * v) * bytes_per_el
                                  + 6 * d["Hv"] * 4))
    if work == "attention":
        # forward reads q (N x heads x head size), k, v (N kv each: the
        # grouped-query heads are read once) and writes ctx; backward
        # reads q, k, v, ctx, dctx and writes dq, dk, dv
        return (attention_flops(config, traffic),
                d["n_attn"] * 6 * N * (d["q"] + d["kv"]) * bytes_per_el)
    if work == "moe_experts":
        # per layer: the routed rows in and out forward (2 A D), rows,
        # their gradient in and the rows' gradient out backward (3 A D);
        # the held experts' weights read forward and backward and their
        # gradient written (3 x held x 3 D F)
        A = expected_assignments(config, traffic)
        weights = d["held"] * 3 * D * d["F"]
        return (expert_flops(config, traffic),
                d["L"] * (5 * A * D + 3 * weights) * bytes_per_el)
    raise KeyError(f"flops/qwen3_next.py knows no work named {work!r}")
