"""Operations and bytes of a Mellum2 training step, from the
configuration's shapes alone.  The work, not an implementation: forward
and backward as the mathematics requires them, a multiply-add counted as
2, nothing recomputed counted, no padded row counted.  Keys are the
published ones (the layers are the first ``num_hidden_layers`` of
``layer_types``); ``num_experts`` is the number of experts HELD and
``deployment.router_width`` the router's width; the traffic gives
``batch`` and ``seq_len``.

The experts are counted at the expectation under a uniform router, as in
``flops/qwen3_next.py``.  Attention is counted by its live scores: QK^T
and PV, 4 hd operations a live score and head forward, twice that
backward.  A full layer's causal row of T keys counts T^2 / 2 scores
(the convention of every family here); a window layer's, whose scores
the kernels skip, counts them exactly: ``W (W + 1) / 2 + (T - W) W`` for
a window of W keys (query i sees keys i - W + 1 .. i).
"""


def _dims(config, traffic):
    c = config
    L = c["num_hidden_layers"]
    kinds = c["layer_types"][:L]
    return {
        "D": c["hidden_size"], "V": c["vocab_size"], "L": L,
        "n_window": sum(k == "sliding_attention" for k in kinds),
        "n_full": sum(k == "full_attention" for k in kinds),
        "W": c["sliding_window"],
        "q": c["num_attention_heads"] * c["head_dim"],
        "kv": c["num_key_value_heads"] * c["head_dim"],
        "F": c["moe_intermediate_size"],
        "held": c["num_experts"],
        "width": c.get("deployment", {}).get("router_width",
                                             c["num_experts"]),
        "k": c["num_experts_per_tok"],
        "B": traffic["batch"], "T": traffic["seq_len"]}


def window_scores(T, W):
    """Live scores of one head and row under a window of W keys."""
    W = min(W, T)
    return W * (W + 1) // 2 + (T - W) * W


def expected_assignments(config, traffic):
    """Assignments to the held experts of ONE layer in one step, under a
    uniform router."""
    d = _dims(config, traffic)
    return d["B"] * d["T"] * d["k"] * d["held"] / d["width"]


def dense_matmul_params(config):
    """Parameters every token multiplies: the attention projections, the
    routers and the output head (the embedding is a gather)."""
    d = _dims(config, {"batch": 0, "seq_len": 0})
    D = d["D"]
    layer = 2 * D * d["q"] + 2 * D * d["kv"] + D * d["width"]
    return d["L"] * layer + D * d["V"]


def expert_flops(config, traffic):
    """The routed experts of one step, forward and backward: three
    (D, F) matrices an assignment, 2 operations a multiply-add, x 3."""
    d = _dims(config, traffic)
    return (6 * 3 * d["D"] * d["F"] * expected_assignments(config, traffic)
            * d["L"])


def full_attention_flops(config, traffic):
    """Causal attention of one step over the full layers."""
    d = _dims(config, traffic)
    return 12 * d["n_full"] * d["T"] * d["T"] // 2 * d["q"] * d["B"]


def window_attention_flops(config, traffic):
    """Attention of one step over the window layers, live scores alone."""
    d = _dims(config, traffic)
    return (12 * d["n_window"] * window_scores(d["T"], d["W"]) * d["q"]
            * d["B"])


def step_flops(config, traffic):
    """Model operations of one training step (forward + backward)."""
    d = _dims(config, traffic)
    return (6 * dense_matmul_params(config) * d["B"] * d["T"]
            + expert_flops(config, traffic)
            + full_attention_flops(config, traffic)
            + window_attention_flops(config, traffic))


def samples_per_step(config, traffic):
    return traffic["batch"]


def kernel_work(config, traffic, work, bytes_per_el=2):
    """(operations, bytes) of one step of the named piece of work, over
    all layers that have it, operands in the compute type."""
    d = _dims(config, traffic)
    N, D = d["B"] * d["T"], d["D"]
    # forward reads q, k, v (the grouped-query heads once) and writes
    # ctx; backward reads q, k, v, ctx, dctx and writes dq, dk, dv
    attn_bytes = 6 * N * (d["q"] + d["kv"]) * bytes_per_el
    if work == "window_attention":
        return (window_attention_flops(config, traffic),
                d["n_window"] * attn_bytes)
    if work == "attention":
        return full_attention_flops(config, traffic), d["n_full"] * attn_bytes
    if work == "moe_experts":
        # per layer: the routed rows in and out forward (2 A D), rows,
        # their gradient in and the rows' gradient out backward (3 A D);
        # the held experts' weights read forward and backward and their
        # gradient written (3 x held x 3 D F)
        A = expected_assignments(config, traffic)
        weights = d["held"] * 3 * D * d["F"]
        return (expert_flops(config, traffic),
                d["L"] * (5 * A * D + 3 * weights) * bytes_per_el)
    raise KeyError(f"flops/mellum2.py knows no work named {work!r}")
