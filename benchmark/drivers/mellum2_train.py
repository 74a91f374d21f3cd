"""Driver of a cell that trains a Mellum2 through the program's functional
step: ``build_spmd_train_step`` given a ``Mellum2Config`` over a
one-device mesh.  The sequence is ``drivers/lfm2_train.py``'s — the
first steps through the window's own call and feed, the device's
counters (``moe_counts``, ``moe_overflow``) read for the checked steps
and once after the window, a step with overflow counted as ``failed`` —
and its window, counters and release are used as they are.  What differs
is the program's configuration of the configuration file, the one
function here: ``setup`` is ``lfm2_train.setup``'s own code run over this
module's ``model_config`` (no copy of it; PERF.md section 7 row 20).
"""
import types

from benchmark.drivers import lfm2_train
from benchmark.drivers.lfm2_train import (  # noqa: F401
    State, _counters, release, window)


def model_config(cfg):
    """The program's configuration of a configuration file.  The keys
    that select a mechanism and have one value the program computes are
    checked, not read past."""
    from paddle_tpu.models.mellum2 import Mellum2Config
    fixed = {"hidden_act": "silu", "attention_bias": False,
             "tie_word_embeddings": False, "use_sliding_window": True,
             "max_window_layers": 0, "norm_topk_prob": True}
    other = {k: cfg[k] for k, v in fixed.items() if cfg.get(k, v) != v}
    L = cfg["num_hidden_layers"]
    if any(t != "sparse" for t in cfg.get("mlp_layer_types", [])[:L]):
        other["mlp_layer_types"] = cfg["mlp_layer_types"]
    full = cfg["rope_parameters"]["full_attention"]
    sliding = cfg["rope_parameters"]["sliding_attention"]
    if (full["rope_type"], sliding["rope_type"]) != ("yarn", "default"):
        other["rope_parameters"] = cfg["rope_parameters"]
    if other:
        raise NotImplementedError(
            f"the Mellum2 model computes {fixed}, sparse MLPs and YaRN on "
            f"the full layers; the configuration asks for {other}")
    dep = cfg.get("deployment", {})
    return Mellum2Config(
        vocab_size=cfg["vocab_size"], hidden_size=cfg["hidden_size"],
        num_hidden_layers=L, layer_types=tuple(cfg["layer_types"]),
        num_attention_heads=cfg["num_attention_heads"],
        num_key_value_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], sliding_window=cfg["sliding_window"],
        rope_theta_sliding=float(sliding["rope_theta"]),
        rope_theta_full=float(full["rope_theta"]),
        yarn=(float(full["factor"]),
              int(full["original_max_position_embeddings"]),
              float(full["beta_fast"]), float(full["beta_slow"])),
        yarn_attention_factor=float(full["attention_factor"]),
        rms_norm_eps=cfg["rms_norm_eps"],
        num_experts=dep.get("router_width", cfg["num_experts"]),
        num_experts_per_tok=cfg["num_experts_per_tok"],
        moe_intermediate_size=cfg["moe_intermediate_size"],
        norm_topk_prob=cfg["norm_topk_prob"],
        num_experts_held=cfg["num_experts"],
        first_expert=dep.get("first_expert", 0),
        moe_rows_factor=cfg["assumed"].get("moe_rows_factor"))


# lfm2_train's setup with this module's model_config in its globals
setup = types.FunctionType(
    lfm2_train.setup.__code__,
    dict(vars(lfm2_train), model_config=model_config), "setup")
