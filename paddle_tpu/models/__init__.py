"""Flagship model family (transformer LM / BERT-style encoder).

The reference ships its NLP flagships out-of-tree (ERNIE) atop
``python/paddle/nn/layer/transformer.py``; this package provides the
equivalent in-tree: an eager nn.Layer GPT (optionally tensor-parallel via
fleet mp layers) and a fully-compiled SPMD trainer that pipelines the
blocks over the ``pp`` mesh axis.  ``lfm2_moe`` is a second functional
model for the same step builder: gated short convolutions, grouped-query
attention and routed experts.  ``qwen3_next`` is a third: gated
delta-rule (linear-attention) layers with a chunked scan, gated softmax
attention, softmax-routed experts beside a gated shared expert.
``joyai_flash`` is a fourth: latent attention (MLA) with a 192-wide q/k
head over a 128-wide v head, sigmoid-routed experts beside an ungated
shared expert, and a multi-token-prediction module scored by the one loss
head a second time.  ``mellum2`` is a fifth: grouped-query attention over
a sliding window in three layers of four and over the whole prefix with
YaRN RoPE in the fourth, softmax-routed experts.  ``sparse_blocks`` holds
what the sparse models share.
"""
import time as _time

_IMPORT_START_NS = _time.time_ns()   # the launch record's ``import`` span

from .gpt import GPTConfig, GPT, GPTBlock  # noqa: F401
from .gpt_spmd import (init_gpt_params, build_spmd_train_step,  # noqa: F401
                       gpt_param_shardings)
from .lfm2_moe import (Lfm2MoeConfig, init_lfm2_moe_params,  # noqa: F401
                       lfm2_moe_param_shardings)
from .qwen3_next import (Qwen3NextConfig,  # noqa: F401
                         init_qwen3_next_params,
                         qwen3_next_param_shardings)
from .joyai_flash import (JoyAIFlashConfig,  # noqa: F401
                          init_joyai_flash_params,
                          joyai_flash_param_shardings)
from .mellum2 import (Mellum2Config, init_mellum2_params,  # noqa: F401
                      mellum2_param_shardings)
from ..profiler import tracer as _tracer  # noqa: E402

# the step builders bring jax.experimental.pallas in, most of this span
_tracer.record_launch("import", _IMPORT_START_NS, _time.time_ns(),
                      fun="paddle_tpu.models")
