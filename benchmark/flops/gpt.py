"""Operations and bytes of a GPT training step, from the configuration's
shapes alone.  The work, not an implementation: forward and backward as
the mathematics requires them, a multiply-add counted as 2, nothing
recomputed counted.  Keys are the published ones (``n_embd``, ``n_layer``,
``n_head``, ``vocab_size``); the traffic gives ``batch`` and ``seq_len``.
"""


def _dims(config, traffic):
    D, L, V = config["n_embd"], config["n_layer"], config["vocab_size"]
    F = config.get("n_inner") or 4 * D
    return D, L, V, F, traffic["batch"], traffic["seq_len"]


def matmul_params(config):
    """Parameters that sit in a matrix multiplication: qkv, out, up and
    down in every layer, and the output head (embeddings are gathers)."""
    D, L, V = config["n_embd"], config["n_layer"], config["vocab_size"]
    F = config.get("n_inner") or 4 * D
    return L * (3 * D * D + D * D + 2 * D * F) + D * V


def attention_flops(config, traffic):
    """Causal attention of one step, all layers: QK^T and PV forward
    (2 * 2*T*T*D a sequence, halved by the mask), twice that backward
    (dV, dP, dQ, dK)."""
    D, L, _V, _F, B, T = _dims(config, traffic)
    return 6 * L * T * D * (B * T)


def step_flops(config, traffic):
    """Model operations of one training step (forward + backward)."""
    _D, _L, _V, _F, B, T = _dims(config, traffic)
    return 6 * matmul_params(config) * B * T + attention_flops(config, traffic)


def samples_per_step(config, traffic):
    return traffic["batch"]


def kernel_work(config, traffic, work, bytes_per_el=2):
    """(operations, bytes) of one step of the named piece of work, over
    all layers, with operands in the compute type (``bytes_per_el``)."""
    D, L, V, _F, B, T = _dims(config, traffic)
    N = B * T
    if work == "attention":
        # forward reads q, k, v and writes ctx (4 N D); backward reads
        # q, k, v, ctx, dctx and writes dq, dk, dv (8 N D)
        return attention_flops(config, traffic), 12 * L * N * D * bytes_per_el
    if work == "loss_head":
        # logits = x W forward; dx = dlogits W^T and dW = x^T dlogits
        # backward: three (N, D, V) matmuls.  x and W read forward and
        # backward, dx and dW written; the logits never need to exist.
        return 6 * N * D * V, (3 * N * D + 3 * D * V) * bytes_per_el
    raise KeyError(f"flops/gpt.py knows no work named {work!r}")
