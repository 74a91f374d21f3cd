"""``pytest benchmark/tests -q`` — the benchmark's own tests, on the CPU
at tiny sizes.  Not part of the repo's tier-1 run."""
import json
import os
import sys
import types

import pytest

os.environ.setdefault("JAX_PLATFORMS", "cpu")
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

TINY_GPT = {
    "family": "gpt", "vocab_size": 160, "n_positions": 32, "n_embd": 64,
    "n_layer": 2, "n_head": 2, "n_inner": None,
    "layer_norm_epsilon": 1e-5,
    "assumed": {"compute_dtype": "bfloat16", "master_dtype": "float32",
                "remat_policy": "ctx",
                "optimizer": {"name": "adamw", "learning_rate": 3e-4,
                              "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
                              "weight_decay": 0.01}}}


GPT_LEAF_AXES = json.load(open(os.path.join(
    ROOT, "benchmark", "workloads", "gpt2-medium.train-t1024.json")))["leaf_axes"]


def make_cell(name, config, cell_file):
    e2e = [{"name": "train_samples_per_s", "unit": "samples/s"},
           {"name": "setup_s", "unit": "s"}]
    return types.SimpleNamespace(
        name=name, chips=1, config=config, cell=cell_file,
        traffic=cell_file["traffic"], end_to_end=e2e, per_layer=[])


@pytest.fixture
def tiny_gpt_cell():
    return make_cell("tiny-gpt.train", TINY_GPT, {
        "driver": "spmd_train", "reference": "gpt", "flops": "gpt",
        "check": "training",
        "traffic": {"batch": 4, "seq_len": 32, "pool": 4, "check_steps": 3},
        "reference_args": {"rows": 2},
        "leaf_axes": GPT_LEAF_AXES,
        "limits": {"grad_gap": 1.0, "change_gap": 1.0},
        "controls": [{"name": "fp8", "precision": "fp8"},
                     {"name": "half_batch", "fault": "half_batch"},
                     {"name": "state_unchanged",
                      "fault": "state_unchanged"}]})


@pytest.fixture
def cpu_devs():
    import jax
    return jax.devices()[:1]
