"""Operations and bytes of a JoyAI-LLM-Flash training step, from the
configuration's shapes alone.  The work, not an implementation: forward
and backward as the mathematics requires them, a multiply-add counted as
2, nothing recomputed counted, no padded row counted.  Keys are the
published ones; ``n_routed_experts`` is the number of experts HELD and
``deployment.router_width`` the router's width (the configuration file
says so); the traffic gives ``batch`` and ``seq_len``.

The step has ``num_hidden_layers`` blocks and ``num_nextn_predict_layers``
multi-token-prediction modules, each of which is one more block (latent
attention + experts) behind ``eh_proj`` and one more pass through the
output head over the positions that have a target two tokens on (T - 1
of a row's T).  The experts are counted at the expectation under a
uniform router — ``tokens x num_experts_per_tok x held / router_width``
assignments a layer — and not at what a run's router chose: the count
must not move with the seed (the cell's counters give a run's real load).
"""


def _dims(config, traffic):
    c = config
    L, n_dense = c["num_hidden_layers"], c["first_k_dense_replace"]
    n_mtp = c["num_nextn_predict_layers"]
    return {
        "D": c["hidden_size"], "V": c["vocab_size"],
        "F": c["intermediate_size"], "Fm": c["moe_intermediate_size"],
        "Fs": c["n_shared_experts"] * c["moe_intermediate_size"],
        "H": c["num_attention_heads"], "rq": c["q_lora_rank"],
        "rkv": c["kv_lora_rank"], "dn": c["qk_nope_head_dim"],
        "dr": c["qk_rope_head_dim"], "dv": c["v_head_dim"],
        "n_dense": n_dense, "n_mtp": n_mtp,
        "n_blocks": L + n_mtp,              # every one has latent attention
        "n_moe": L - n_dense + n_mtp,       # the module's layer is sparse
        "held": c["n_routed_experts"],
        "width": c.get("deployment", {}).get("router_width",
                                             c["n_routed_experts"]),
        "k": c["num_experts_per_tok"],
        "B": traffic["batch"], "T": traffic["seq_len"]}


def expected_assignments(config, traffic):
    """Assignments to the held experts of ONE expert layer in one step,
    under a uniform router."""
    d = _dims(config, traffic)
    return d["B"] * d["T"] * d["k"] * d["held"] / d["width"]


def mla_params(config):
    """The five projections of one latent-attention mixer."""
    d = _dims(config, {"batch": 0, "seq_len": 0})
    D, H = d["D"], d["H"]
    return (D * d["rq"] + d["rq"] * H * (d["dn"] + d["dr"])
            + D * (d["rkv"] + d["dr"]) + d["rkv"] * H * (d["dn"] + d["dv"])
            + H * d["dv"] * D)


def dense_matmul_params(config):
    """Parameters every token multiplies, the output head apart (it sees
    a different number of rows a depth): the mixers, the dense FFNs, the
    routers, the shared experts and ``eh_proj`` (the embedding is a
    gather)."""
    d = _dims(config, {"batch": 0, "seq_len": 0})
    D = d["D"]
    return (d["n_blocks"] * mla_params(config)
            + d["n_dense"] * 3 * D * d["F"]
            + d["n_moe"] * (D * d["width"] + 3 * D * d["Fs"])
            + d["n_mtp"] * 2 * D * D)


def head_rows(config, traffic):
    """Rows the output head scores in one step: every position for the
    next token, and a depth further every position but a row's last."""
    d = _dims(config, traffic)
    return d["B"] * d["T"] + d["n_mtp"] * d["B"] * (d["T"] - 1)


def expert_flops(config, traffic):
    """The routed experts of one step, forward and backward: three
    (D, Fm) matrices an assignment, 2 operations a multiply-add, x 3."""
    d = _dims(config, traffic)
    return (6 * 3 * d["D"] * d["Fm"] * expected_assignments(config, traffic)
            * d["n_moe"])


def attention_flops(config, traffic):
    """Causal attention of one step over every block: QK^T over the q/k
    head size and PV over the v head size forward (2 B H T^2 (dn + dr +
    dv), halved by the mask), twice that backward."""
    d = _dims(config, traffic)
    return (3 * d["n_blocks"] * d["B"] * d["H"] * d["T"] * d["T"]
            * (d["dn"] + d["dr"] + d["dv"]))


def step_flops(config, traffic):
    """Model operations of one training step (forward + backward)."""
    d = _dims(config, traffic)
    return (6 * dense_matmul_params(config) * d["B"] * d["T"]
            + 6 * d["D"] * d["V"] * head_rows(config, traffic)
            + expert_flops(config, traffic)
            + attention_flops(config, traffic))


def samples_per_step(config, traffic):
    return traffic["batch"]


def kernel_work(config, traffic, work, bytes_per_el=2):
    """(operations, bytes) of one step of the named piece of work, over
    all layers that have it, operands in the compute type."""
    d = _dims(config, traffic)
    N, D = d["B"] * d["T"], d["D"]
    if work == "mla_attn":
        # forward reads q, k (dn + dr a head) and v and writes ctx (dv a
        # head); backward reads q, k, v, ctx, dctx and writes dq, dk, dv:
        # each once through HBM, the heads' shared rotated key part
        # counted a head as the kernels see it
        qk, dv = d["dn"] + d["dr"], d["dv"]
        return (attention_flops(config, traffic),
                d["n_blocks"] * N * d["H"] * (6 * qk + 5 * dv)
                * bytes_per_el)
    if work == "moe_experts":
        # per expert layer: the routed rows in and out forward (2 A D),
        # rows, their gradient in and the rows' gradient out backward
        # (3 A D); the held experts' weights read forward and backward and
        # their gradient written (3 x held x 3 D Fm)
        A = expected_assignments(config, traffic)
        weights = d["held"] * 3 * D * d["Fm"]
        return (expert_flops(config, traffic),
                d["n_moe"] * (5 * A * D + 3 * weights) * bytes_per_el)
    raise KeyError(f"flops/joyai_flash.py knows no work named {work!r}")
