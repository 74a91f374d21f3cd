"""Operations and bytes of an LFM2-MoE training step, from the
configuration's shapes alone.  The work, not an implementation: forward
and backward as the mathematics requires them, a multiply-add counted as
2, nothing recomputed counted, no padded row counted.  Keys are the
published ones; ``num_experts`` is the number of experts HELD and
``deployment.router_width`` the router's width (the configuration file
says so); the traffic gives ``batch`` and ``seq_len``.

Sparse operations: a token pays for the experts it reaches, not for all
of them.  The experts are counted at the expectation under a uniform
router — ``tokens x num_experts_per_tok x held / router_width``
assignments a layer — and not at what a run's router chose: the count
must not move with the seed.  The cell's counters give the real load of
a run (``info.window.moe`` of the result line).
"""


def _dims(config, traffic):
    D = config["hidden_size"]
    kinds = list(config["layer_types"])
    n_dense = config["num_dense_layers"]
    hd = D // config["num_attention_heads"]
    return {
        "D": D, "V": config["vocab_size"], "F": config["intermediate_size"],
        "Fm": config["moe_intermediate_size"],
        "kv": config["num_key_value_heads"] * hd,
        "taps": config["conv_L_cache"],
        "n_conv": kinds.count("conv"),
        "n_attn": kinds.count("full_attention"),
        "n_dense": n_dense, "n_moe": len(kinds) - n_dense,
        "held": config["num_experts"],
        "width": config.get("deployment", {}).get(
            "router_width", config["num_experts"]),
        "k": config["num_experts_per_tok"],
        "B": traffic["batch"], "T": traffic["seq_len"]}


def expected_assignments(config, traffic):
    """Assignments to the held experts of ONE expert layer in one step,
    under a uniform router."""
    d = _dims(config, traffic)
    return d["B"] * d["T"] * d["k"] * d["held"] / d["width"]


def dense_matmul_params(config):
    """Parameters every token multiplies: the operators' projections, the
    dense FFNs, the routers and the output head (the embedding is a
    gather; the 3-tap convolution is counted with them, a multiply-add a
    tap and channel)."""
    d = _dims(config, {"batch": 0, "seq_len": 0})
    D = d["D"]
    conv = 3 * D * D + D * D + d["taps"] * D
    attn = 2 * D * D + 2 * D * d["kv"]
    return (d["n_conv"] * conv + d["n_attn"] * attn
            + d["n_dense"] * 3 * D * d["F"] + d["n_moe"] * D * d["width"]
            + D * d["V"])


def expert_flops(config, traffic):
    """The routed experts of one step, forward and backward: three
    (D, Fm) matrices an assignment, 2 operations a multiply-add, x 3."""
    d = _dims(config, traffic)
    return (6 * 3 * d["D"] * d["Fm"] * expected_assignments(config, traffic)
            * d["n_moe"])


def attention_flops(config, traffic):
    """Causal attention of one step over the attention layers: QK^T and
    PV forward (2 * 2*T*T*D a sequence, halved by the mask), twice that
    backward.  Grouped-query heads change the bytes, not the operations."""
    d = _dims(config, traffic)
    return 6 * d["n_attn"] * d["T"] * d["D"] * (d["B"] * d["T"])


def step_flops(config, traffic):
    """Model operations of one training step (forward + backward)."""
    d = _dims(config, traffic)
    return (6 * dense_matmul_params(config) * d["B"] * d["T"]
            + expert_flops(config, traffic)
            + attention_flops(config, traffic))


def samples_per_step(config, traffic):
    return traffic["batch"]


def kernel_work(config, traffic, work, bytes_per_el=2):
    """(operations, bytes) of one step of the named piece of work, over
    all layers that have it, operands in the compute type."""
    d = _dims(config, traffic)
    N, D = d["B"] * d["T"], d["D"]
    if work == "attention":
        # forward reads q (N D), k, v (N kv each: the grouped-query heads
        # are read once, not once a query head) and writes ctx; backward
        # reads q, k, v, ctx, dctx and writes dq, dk, dv
        return (attention_flops(config, traffic),
                d["n_attn"] * 6 * N * (D + d["kv"]) * bytes_per_el)
    if work == "moe_experts":
        # per expert layer: the routed rows in and out forward (2 A D),
        # rows, their gradient in and the rows' gradient out backward
        # (3 A D); the held experts' weights read forward and backward and
        # their gradient written (3 x held x 3 D Fm).  The (A, Fm)
        # intermediates need not leave the chip's fast memory
        A = expected_assignments(config, traffic)
        weights = d["held"] * 3 * D * d["Fm"]
        return (expert_flops(config, traffic),
                d["n_moe"] * (5 * A * D + 3 * weights) * bytes_per_el)
    raise KeyError(f"flops/lfm2_moe.py knows no work named {work!r}")
