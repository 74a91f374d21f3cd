"""Hand-written TPU kernels (pallas).

Reference parity: these play the role of the reference's hand-authored
CUDA in ``operators/fused/`` (fused_attention_op.cu, fused_dropout chains)
and ``operators/kernel_primitives/`` — the ops where HBM bandwidth or
softmax-rescaling tricks beat what the compiler fuses on its own.

The kernels: ``flash_attention`` (eleven attention kernels behind one
plan; the resident pair also at a v head size other than q's and k's:
latent attention's 192 over 128), ``fused_ln``, ``softmax_xent`` (the
fused loss head's forward, an optional weight a row), ``gated_delta_rule``
(the linear-attention recurrence in chunks: the prep with its triangular
inverse in VMEM, the loop with the state in VMEM, and their reverse
passes) and ``causal_conv`` (the convolution, SiLU and q / k L2 norms in
front of that rule, token-major: a forward writing q, k, v, a backward).

Every place that chooses between a Mosaic kernel and XLA math asks this
module, and records what it chose:

- on a TPU a pallas kernel is always compiled (never interpreted);
- off-TPU the XLA math runs, unless ``PADDLE_PALLAS_FORCE=1`` asks for
  the kernel in interpret mode (the kernel unit tests);
- each choice bumps ``pallas.<kernel>.<impl>`` (``mosaic`` /
  ``interpret`` / ``xla``) at trace time, so a run can assert which
  implementation its programs hold (``selections()``).
"""
import contextlib
import os
import threading

import jax

from ...profiler import metrics as _metrics

__all__ = ["flash_attention", "on_tpu", "enabled", "note", "selections",
           "shard_kernel", "kernel_mesh", "current_kernel_mesh"]


def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def enabled() -> bool:
    """Whether pallas kernels are taken at all: on a TPU, or anywhere
    under ``PADDLE_PALLAS_FORCE=1`` (interpret mode off-TPU)."""
    return on_tpu() or os.environ.get("PADDLE_PALLAS_FORCE") == "1"


def note(kernel: str, use_pallas: bool) -> bool:
    """Count one selection for ``kernel``; returns ``interpret`` for the
    ``pallas_call`` (False whenever the XLA math was chosen)."""
    interpret = use_pallas and not on_tpu()
    impl = "xla" if not use_pallas else \
        "interpret" if interpret else "mosaic"
    _metrics.counter(f"pallas.{kernel}.{impl}").inc()
    return interpret


def selections() -> dict:
    """``{"<kernel>.<impl>": count}`` of every selection so far."""
    return {k[len("pallas."):]: v
            for k, v in _metrics.snapshot().items()
            if k.startswith("pallas.") and v}


def shard_kernel(fn, mesh, in_specs, out_specs):
    """``fn`` run per shard over every mesh axis GSPMD still owns.

    A Mosaic custom call cannot be partitioned automatically ("Mosaic
    kernels cannot be automatically partitioned"), so under a mesh of
    more than one device the kernel sits inside a ``shard_map`` whose
    specs name the axes that shard its operands; axes the specs leave
    out see replicated operands.  Inside an enclosing partial-manual
    ``shard_map`` (the pp pipeline) only the axes still automatic are
    mapped, over the context mesh.
    """
    ctx = jax.sharding.get_abstract_mesh()
    if not ctx.empty:
        mesh, auto = None, frozenset(ctx.auto_axes)
    else:
        auto = frozenset(mesh.axis_names) if mesh is not None \
            and mesh.size > 1 else frozenset()
    if not auto:
        return fn
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, axis_names=auto,
                         check_vma=False)


_mesh_scope = threading.local()


@contextlib.contextmanager
def kernel_mesh(mesh, batch_axes=(), head_axes=()):
    """Tell the Layer-API ops traced inside (``scaled_dot_product_
    attention``) which mesh their operands live on and which of its axes
    shard batch and heads — the functional entries take these as
    arguments; a ``Layer.forward`` has nowhere to pass them.
    ``DataParallel.forward`` enters it."""
    prev = getattr(_mesh_scope, "spec", None)
    _mesh_scope.spec = (mesh, tuple(batch_axes), tuple(head_axes))
    try:
        yield
    finally:
        _mesh_scope.spec = prev


def current_kernel_mesh():
    """``(mesh, batch_axes, head_axes)`` of the enclosing
    :func:`kernel_mesh`, or None."""
    return getattr(_mesh_scope, "spec", None)


from .flash_attention import flash_attention  # noqa: E402,F401
