"""paddle_tpu: a TPU-native deep-learning framework.

Brand-new implementation of the capability surface of kerwinner/Paddle
(PaddlePaddle ~v2.2), designed TPU-first on JAX/XLA/pallas/pjit:

- eager (dygraph) runtime with tape autograd over jax.vjp
- jit/static path (``paddle_tpu.jit.to_static`` == traced+compiled XLA)
- nn module system, optimizers, AMP, DataLoader, Model.fit hapi
- distributed: device-mesh topology, named-axis collectives, DP/TP/PP/
  ZeRO-sharding/recompute, sequence-parallel ring attention
- pallas kernels for the fused hot paths (flash attention, fused LN)

The public namespace mirrors ``paddle.*`` so reference users can switch.
"""
from __future__ import annotations

import time as _time

_IMPORT_START_NS = _time.time_ns()   # the launch record's ``import`` span

__version__ = "0.1.0"

# core surface
from .core.tensor import Tensor, Parameter, to_tensor  # noqa: F401
from .core.place import (  # noqa: F401
    CPUPlace, TPUPlace, CUDAPinnedPlace, set_device, get_device,
    device_count, is_compiled_with_tpu,
)
from .core.autograd import no_grad, enable_grad, set_grad_enabled, \
    is_grad_enabled, grad  # noqa: F401
from .core.random import seed, get_rng_state, set_rng_state  # noqa: F401
from .core import dtype as _dtype_mod
from .core import errors  # noqa: F401  (enforce taxonomy, enforce.h:422)
from .core.dtype import (  # noqa: F401
    float32, float64, float16, bfloat16, int8, int16, int32, int64, uint8,
    bool_, complex64, complex128,
)

# whole op surface re-exported at top level (paddle.* style)
from .ops import *  # noqa: F401,F403
from . import ops  # noqa: F401

# subsystem namespaces
# NB: `from .ops import *` above binds the ops.linalg submodule onto this
# package under the name `linalg`; rebind to the real paddle_tpu.linalg
# namespace module (which re-exports the op set and adds cond etc.).
import importlib as _importlib

linalg = _importlib.import_module(".linalg", __name__)
cond = linalg.cond  # paddle.cond == paddle.linalg.cond (reference export)
from . import fft  # noqa: F401
from . import signal  # noqa: F401
from . import nn  # noqa: F401
from . import optimizer  # noqa: F401
from . import amp  # noqa: F401
from . import io  # noqa: F401
from . import metric  # noqa: F401
from . import jit  # noqa: F401
from . import static  # noqa: F401
from . import inference  # noqa: F401
from . import serving  # noqa: F401
from . import vision  # noqa: F401
from . import distributed  # noqa: F401
from . import autograd  # noqa: F401
from . import distribution  # noqa: F401
from . import text  # noqa: F401
from . import hub  # noqa: F401
from . import sparsity  # noqa: F401
from . import reader  # noqa: F401
from . import dataset  # noqa: F401
from . import callbacks  # noqa: F401
from . import sysconfig  # noqa: F401
from . import onnx  # noqa: F401
from .batch import batch  # noqa: F401
from . import regularizer  # noqa: F401
from . import device  # noqa: F401
from . import incubate  # noqa: F401
from . import profiler as profiler_mod  # noqa: F401
from . import utils  # noqa: F401

from .nn.param_attr import ParamAttr  # noqa: F401
from .framework_io import save, load  # noqa: F401
from .hapi.model import Model  # noqa: F401
from .hapi import summary, flops  # noqa: F401
from .utils.flags import get_flags, set_flags  # noqa: F401

# paddle.disable_static / enable_static compatibility: the dygraph mode is
# the default; enable_static() switches the `static` module's executor into
# program-capture mode.
from .static.mode import enable_static, disable_static, in_dynamic_mode  # noqa: F401


def is_compiled_with_cuda() -> bool:
    return False


def is_compiled_with_rocm() -> bool:
    return False


def is_compiled_with_xpu() -> bool:
    return False


def is_compiled_with_npu() -> bool:
    return False


profiler_mod.tracer.record_launch("import", _IMPORT_START_NS,
                                  _time.time_ns(), fun="paddle_tpu")
