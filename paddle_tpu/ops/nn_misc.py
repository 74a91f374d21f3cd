"""Embedding, dropout, attention and misc nn functional ops.

Reference parity: ``operators/lookup_table_v2_op.*`` (embedding),
``operators/dropout_op.*``, ``operators/fused/fused_attention_op.cu`` and
``operators/sparse_attention_op.cc`` — on TPU the attention hot path is a
pallas flash-attention kernel (ops/pallas/flash_attention.py) with an XLA
fallback here.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from ..core.dispatch import dispatch, get_kernel, register_kernel
from ..core.random import default_generator
from ..core.tensor import Tensor, to_tensor

__all__ = [
    "embedding", "dropout", "dropout2d", "dropout3d", "alpha_dropout",
    "linear", "bilinear", "scaled_dot_product_attention", "sparse_attention",
    "sequence_mask", "diag_embed", "cosine_similarity", "pairwise_distance",
    "affine_grid", "npair_loss", "temporal_shift", "class_center_sample",
    "affine_channel", "nce",
]


def embedding(x, weight, padding_idx=None, sparse=False, name=None):
    x, weight = to_tensor(x), to_tensor(weight)

    def impl(idx, w):
        out = jnp.take(w, idx, axis=0)
        if padding_idx is not None and padding_idx >= 0:
            mask = (idx != padding_idx)[..., None]
            out = out * mask.astype(out.dtype)
        return out

    from ..core import autograd as _ag
    if sparse and _ag.is_grad_enabled() and not weight.stop_gradient \
            and not isinstance(weight._data, jax.core.Tracer):
        # SelectedRows backward (reference selected_rows.h +
        # lookup_table_v2_op grad is_sparse branch): the weight gradient
        # is (rows=ids, values=cotangent slices) — the dense (V, D) grad
        # never materialises.  Eager-only: under jit the dense path's
        # scatter-add fuses anyway.
        from ..core.selected_rows import SelectedRows
        ids = x._data
        out_arr = impl(ids, weight._data)
        D = weight.shape[1]
        V = weight.shape[0]

        def vjp_fn(cot):
            rows = ids.reshape(-1)
            vals = jnp.asarray(cot).reshape(-1, D)
            if padding_idx is not None and padding_idx >= 0:
                keep = (rows != padding_idx)[:, None]
                vals = vals * keep.astype(vals.dtype)
            import numpy as _np
            gx = _np.zeros(ids.shape, jax.dtypes.float0)
            return gx, SelectedRows(rows, vals, (V, D))

        node = _ag.GradNode("embedding_sparse_grad", vjp_fn, [x, weight],
                            [False, True],
                            [(out_arr.shape, out_arr.dtype)], False)
        t = Tensor(out_arr, stop_gradient=False)
        t._grad_node = node
        t._output_index = 0
        return t
    return dispatch("embedding", impl, (x, weight), {})


def dropout(x, p=0.5, axis=None, training=True, mode="upscale_in_train",
            name=None):
    x = to_tensor(x)
    if not training or p == 0.0:
        if mode == "downscale_in_infer" and not training:
            return dispatch("dropout_infer", lambda a: a * (1.0 - p), (x,), {})
        return x
    key = default_generator.next_key()

    def impl(a):
        shape = list(a.shape)
        if axis is not None:
            axes = [axis] if isinstance(axis, int) else list(axis)
            shape = [s if i in axes else 1 for i, s in enumerate(shape)]
        keep = jax.random.bernoulli(key, 1.0 - p, tuple(shape))
        if mode == "upscale_in_train":
            return jnp.where(keep, a / (1.0 - p), 0.0).astype(a.dtype)
        return jnp.where(keep, a, 0.0).astype(a.dtype)
    return dispatch("dropout", impl, (x,), {})


def dropout2d(x, p=0.5, training=True, data_format="NCHW", name=None):
    axis = [0, 1] if data_format == "NCHW" else [0, 3]
    return dropout(x, p=p, axis=axis, training=training)


def dropout3d(x, p=0.5, training=True, data_format="NCDHW", name=None):
    axis = [0, 1] if data_format == "NCDHW" else [0, 4]
    return dropout(x, p=p, axis=axis, training=training)


def alpha_dropout(x, p=0.5, training=True, name=None):
    x = to_tensor(x)
    if not training or p == 0.0:
        return x
    key = default_generator.next_key()
    alpha = 1.6732632423543772
    scale = 1.0507009873554805
    alpha_p = -alpha * scale

    def impl(a):
        keep = jax.random.bernoulli(key, 1.0 - p, a.shape)
        q = 1.0 - p
        a_coef = (q + alpha_p ** 2 * q * p) ** -0.5
        b_coef = -a_coef * alpha_p * p
        return a_coef * jnp.where(keep, a, alpha_p) + b_coef
    return dispatch("alpha_dropout", impl, (x,), {})


def linear(x, weight, bias=None, name=None):
    """y = x @ W (+ b).  Weight layout (in, out) — reference mul_op/fc."""
    x, weight = to_tensor(x), to_tensor(weight)
    tensors = [x, weight] + ([to_tensor(bias)] if bias is not None else [])

    def impl(a, w, *b):
        out = jnp.matmul(a, w)
        return out + b[0] if b else out
    return dispatch("linear", impl, tensors, {})


def bilinear(x1, x2, weight, bias=None, name=None):
    x1, x2, weight = to_tensor(x1), to_tensor(x2), to_tensor(weight)
    tensors = [x1, x2, weight] + ([to_tensor(bias)] if bias is not None else [])

    def impl(a, b, w, *bs):
        out = jnp.einsum("bi,oij,bj->bo", a, w, b)
        return out + bs[0] if bs else out
    return dispatch("bilinear", impl, tensors, {})


def _sdpa_xla(q, k, v, *rest, causal=False, scale=None, dropout_p=0.0,
              dropout_key=None, has_mask=False, mesh_spec=None):
    """Reference attention math (XLA fused).  q/k/v: (B, S, H, D).
    ``mesh_spec`` is for the pallas registration: GSPMD partitions this
    math by itself."""
    d = q.shape[-1]
    s = scale if scale is not None else 1.0 / np.sqrt(d)
    qh = jnp.swapaxes(q, 1, 2)  # B,H,S,D
    kh = jnp.swapaxes(k, 1, 2)
    vh = jnp.swapaxes(v, 1, 2)
    logits = jnp.einsum("bhqd,bhkd->bhqk", qh, kh) * s
    if has_mask:
        logits = logits + rest[0]
    if causal:
        sq, sk = logits.shape[-2], logits.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        logits = jnp.where(mask, logits, jnp.finfo(logits.dtype).min)
    probs = jax.nn.softmax(logits, axis=-1)
    if dropout_p > 0.0 and dropout_key is not None:
        keep = jax.random.bernoulli(dropout_key, 1.0 - dropout_p, probs.shape)
        probs = jnp.where(keep, probs / (1.0 - dropout_p), 0.0)
    out = jnp.einsum("bhqk,bhkd->bhqd", probs, vh)
    return jnp.swapaxes(out, 1, 2)


register_kernel("scaled_dot_product_attention", "xla")(_sdpa_xla)


def _sdpa_pallas(q, k, v, *rest, causal=False, scale=None, dropout_p=0.0,
                 dropout_key=None, has_mask=False, mesh_spec=None):
    """Flash-attention pallas kernel (ops/pallas/flash_attention.py);
    the kernels take no mask and no dropout, so those variants run the
    XLA math (counted as ``flash_attention.xla``)."""
    if has_mask or dropout_p > 0.0:
        from .pallas import note
        note("flash_attention", False)
        return _sdpa_xla(q, k, v, *rest, causal=causal, scale=scale,
                         dropout_p=dropout_p, dropout_key=dropout_key,
                         has_mask=has_mask)
    from .pallas.flash_attention import flash_attention
    # under a mesh the kernels run per shard (``pallas.kernel_mesh``,
    # entered by DataParallel.forward); sharded operands with no mesh
    # given fail at lowering — "Mosaic kernels cannot be automatically
    # partitioned" — rather than quietly taking other math
    mesh, batch_axes, head_axes = mesh_spec or (None, (), ())
    return flash_attention(q, k, v, causal=causal, scale=scale, mesh=mesh,
                           batch_axes=batch_axes, head_axes=head_axes)


register_kernel("scaled_dot_product_attention", "pallas")(_sdpa_pallas)


def scaled_dot_product_attention(query, key, value, attn_mask=None,
                                 dropout_p=0.0, is_causal=False,
                                 training=True, scale=None, name=None):
    """Inputs (B, S, H, D) paddle-style; pallas flash kernel used on TPU."""
    query, key, value = to_tensor(query), to_tensor(key), to_tensor(value)
    tensors = [query, key, value]
    has_mask = attn_mask is not None
    if has_mask:
        tensors.append(to_tensor(attn_mask))
    dkey = default_generator.next_key() if (dropout_p > 0.0 and training) else None
    # pass the registered xla kernel + static attrs through dispatch's
    # kwargs — dispatch itself swaps in the pallas registration when
    # preferred_backend() says so (core/dispatch.py)
    impl = get_kernel("scaled_dot_product_attention", "xla")
    from .pallas import current_kernel_mesh
    return dispatch("scaled_dot_product_attention", impl, tensors,
                    dict(causal=is_causal, scale=scale,
                         dropout_p=dropout_p if training else 0.0,
                         dropout_key=dkey, has_mask=has_mask,
                         mesh_spec=current_kernel_mesh()))


def sparse_attention(query, key, value, sparse_csr_offset, sparse_csr_columns,
                     key_padding_mask=None, attn_mask=None, name=None):
    """Block-sparse attention (reference operators/sparse_attention_op.cc:71).
    TPU path: dense flash attention with the sparsity pattern applied as a
    mask — XLA/Mosaic handles the skipped blocks; a true block-sparse pallas
    kernel is a future optimisation."""
    query, key, value = to_tensor(query), to_tensor(key), to_tensor(value)
    offs = np.asarray(to_tensor(sparse_csr_offset)._data)
    cols = np.asarray(to_tensor(sparse_csr_columns)._data)

    def impl(q, k, v):
        b, h, s, d = q.shape
        mask = np.zeros((s, s), dtype=bool)
        row_off = offs.reshape(-1)[: s + 1]
        col = cols.reshape(-1)
        for i in range(s):
            mask[i, col[row_off[i]:row_off[i + 1]]] = True
        m = jnp.asarray(mask)
        logits = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(d)
        logits = jnp.where(m, logits, jnp.finfo(logits.dtype).min)
        probs = jax.nn.softmax(logits, axis=-1)
        return jnp.einsum("bhqk,bhkd->bhqd", probs, v)
    return dispatch("sparse_attention", impl, (query, key, value), {})


def sequence_mask(x, maxlen=None, dtype="int64", name=None):
    from ..core.dtype import dtype_to_jnp
    x = to_tensor(x)
    if maxlen is None:
        maxlen = int(np.asarray(x._data).max())
    rng = jnp.arange(maxlen)
    out = (rng[None, :] < x._data[..., None]).astype(dtype_to_jnp(dtype))
    return Tensor(out)


def diag_embed(input, offset=0, dim1=-2, dim2=-1):
    input = to_tensor(input)

    def impl(a):
        n = a.shape[-1] + abs(offset)
        out = jnp.zeros(a.shape[:-1] + (n, n), a.dtype)
        idx = jnp.arange(a.shape[-1])
        r = idx + max(-offset, 0)
        c = idx + max(offset, 0)
        out = out.at[..., r, c].set(a)
        src = list(range(out.ndim))
        d1, d2 = dim1 % out.ndim, dim2 % out.ndim
        return jnp.moveaxis(out, [out.ndim - 2, out.ndim - 1], [d1, d2])
    return dispatch("diag_embed", impl, (input,), {})


def cosine_similarity(x1, x2, axis=1, eps=1e-8):
    x1, x2 = to_tensor(x1), to_tensor(x2)

    def impl(a, b):
        num = jnp.sum(a * b, axis=axis)
        den = jnp.linalg.norm(a, axis=axis) * jnp.linalg.norm(b, axis=axis)
        return num / jnp.maximum(den, eps)
    return dispatch("cosine_similarity", impl, (x1, x2), {})


def pairwise_distance(x, y, p=2.0, epsilon=1e-6, keepdim=False, name=None):
    x, y = to_tensor(x), to_tensor(y)

    def impl(a, b):
        d = a - b + epsilon
        return jnp.power(jnp.sum(jnp.power(jnp.abs(d), p), axis=-1,
                                 keepdims=keepdim), 1.0 / p)
    return dispatch("pairwise_distance", impl, (x, y), {})


def affine_grid(theta, out_shape, align_corners=True, name=None):
    theta = to_tensor(theta)
    if isinstance(out_shape, Tensor):
        out_shape = out_shape.tolist()
    n, c, h, w = [int(s) for s in out_shape]

    def impl(th):
        if align_corners:
            xs = jnp.linspace(-1, 1, w)
            ys = jnp.linspace(-1, 1, h)
        else:
            xs = jnp.linspace(-1 + 1.0 / w, 1 - 1.0 / w, w)
            ys = jnp.linspace(-1 + 1.0 / h, 1 - 1.0 / h, h)
        gy, gx = jnp.meshgrid(ys, xs, indexing="ij")
        ones = jnp.ones_like(gx)
        base = jnp.stack([gx, gy, ones], axis=-1)  # h,w,3
        return jnp.einsum("hwk,nak->nhwa", base, th)
    return dispatch("affine_grid", impl, (theta,), {})


def npair_loss(anchor, positive, labels, l2_reg=0.002):
    anchor, positive, labels = (to_tensor(anchor), to_tensor(positive),
                                to_tensor(labels))

    def impl(a, p, y):
        y = y.reshape(-1, 1)
        same = (y == y.T).astype(a.dtype)
        same = same / jnp.sum(same, axis=1, keepdims=True)
        logits = jnp.matmul(a, p.T)
        logp = jax.nn.log_softmax(logits, axis=1)
        ce = -jnp.mean(jnp.sum(same * logp, axis=1))
        reg = l2_reg * (jnp.mean(jnp.sum(jnp.square(a), axis=1)) +
                        jnp.mean(jnp.sum(jnp.square(p), axis=1))) * 0.25
        return ce + reg
    return dispatch("npair_loss", impl, (anchor, positive, labels), {})


def temporal_shift(x, seg_num, shift_ratio=0.25, data_format="NCHW", name=None):
    x = to_tensor(x)

    def impl(a):
        if data_format == "NHWC":
            a = jnp.transpose(a, (0, 3, 1, 2))
        nt, c, h, w = a.shape
        n = nt // seg_num
        a = a.reshape(n, seg_num, c, h, w)
        fold = int(c * shift_ratio)
        left = jnp.concatenate([a[:, 1:, :fold], jnp.zeros_like(a[:, :1, :fold])], axis=1)
        right = jnp.concatenate([jnp.zeros_like(a[:, :1, fold:2 * fold]),
                                 a[:, :-1, fold:2 * fold]], axis=1)
        mid = a[:, :, 2 * fold:]
        out = jnp.concatenate([left, right, mid], axis=2).reshape(nt, c, h, w)
        if data_format == "NHWC":
            out = jnp.transpose(out, (0, 2, 3, 1))
        return out
    return dispatch("temporal_shift", impl, (x,), {})


def class_center_sample(label, num_classes, num_samples, group=None):
    raise NotImplementedError(
        "class_center_sample: PS-style sampled softmax not yet on TPU path")


def affine_channel(x, scale, bias, data_layout="NCHW", name=None):
    """Per-channel scale+shift (reference operators/affine_channel_op.cc:1
    — frozen-BN replacement in detection backbones)."""
    x, scale, bias = to_tensor(x), to_tensor(scale), to_tensor(bias)

    def impl(a, s, b):
        if data_layout in ("NCHW", "NCDHW"):
            shape = (1, -1) + (1,) * (a.ndim - 2)
        else:
            shape = (1,) * (a.ndim - 1) + (-1,)
        return a * s.reshape(shape) + b.reshape(shape)

    return dispatch("affine_channel", impl, (x, scale, bias), {})


def nce(input, label, weight, bias=None, num_total_classes=None,
        num_neg_samples=10, sampler="uniform", sample_weight=None,
        custom_dist=None, seed=None, name=None):
    """Noise-contrastive estimation loss (reference operators/nce_op.h:80):
    per row i with true class t and negatives {s_k}:
    o = sigmoid(x_i . w_c + b_c); q = P_sampler(c) * num_neg;
    cost = -log(o/(o+q)) for true, -log(q/(o+q)) for sampled.

    TPU translation: negatives are sampled host-side per call (like the
    reference's CPU Sampler), then the cost is one fused device gather +
    matmul — differentiable through w/b/input via jax.vjp.
    Returns per-row cost [N, 1]."""
    input, weight = to_tensor(input), to_tensor(weight)
    lab_np = np.asarray(to_tensor(label)._data)
    N = int(input.shape[0])
    # reference supports [N, num_true] labels (nce_op.h PrepareSamples)
    lab_np = lab_np.reshape(N, -1)
    num_true = lab_np.shape[1]
    V = int(num_total_classes if num_total_classes is not None
            else weight.shape[0])
    if seed is None:
        import jax.random as _jr
        seed = int(_jr.randint(default_generator.next_key(), (),
                               0, 2**31 - 1, jnp.int32))
    rng = np.random.RandomState(seed)
    if sampler == "uniform":
        negs = rng.randint(0, V, size=(N, num_neg_samples))
        def q(c):
            return np.full(c.shape, 1.0 / V)
    elif sampler == "log_uniform":
        # P(k) = log((k+2)/(k+1)) / log(V+1)  (TF/paddle LogUniformSampler)
        u = rng.rand(N, num_neg_samples)
        negs = (np.exp(u * np.log(V + 1.0)) - 1.0).astype(np.int64)
        negs = np.clip(negs, 0, V - 1)
        def q(c):
            c = c.astype(np.float64)
            return (np.log((c + 2.0) / (c + 1.0)) / np.log(V + 1.0))
    elif sampler == "custom_dist":
        probs = np.asarray(custom_dist, np.float64)
        probs = probs / probs.sum()
        negs = np.stack([rng.choice(V, size=num_neg_samples, p=probs)
                         for _ in range(N)])
        def q(c):
            return probs[c]
    else:
        raise ValueError(f"unknown sampler {sampler!r}")
    samples = np.concatenate([lab_np, negs], axis=1)
    qv = (q(samples) * num_neg_samples).astype(np.float32)
    samples_j = jnp.asarray(samples)
    q_j = jnp.asarray(qv)

    args = [input, weight]
    has_bias = bias is not None
    if has_bias:
        args.append(to_tensor(bias))
    if sample_weight is not None:
        args.append(to_tensor(sample_weight))

    def impl(x, w, *rest):
        i = 0
        b = rest[i] if has_bias else None
        i += int(has_bias)
        sw = rest[i] if sample_weight is not None else None
        ws = w[samples_j]                       # [N, 1+S, D]
        logits = jnp.einsum("nd,nsd->ns", x, ws)
        if b is not None:
            logits = logits + b[samples_j]
        o = jax.nn.sigmoid(logits)
        t = num_true
        cost_true = -jnp.log(o[:, :t] / (o[:, :t] + q_j[:, :t]))
        cost_neg = -jnp.log(q_j[:, t:] / (o[:, t:] + q_j[:, t:]))
        cost = jnp.sum(cost_true, axis=1) + jnp.sum(cost_neg, axis=1)
        if sw is not None:
            cost = cost * sw.reshape(-1)
        return cost.reshape(-1, 1)

    return dispatch("nce", impl, tuple(args), {})
