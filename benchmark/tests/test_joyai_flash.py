"""The JoyAI-LLM-Flash family of the benchmark on the CPU at a tiny size:
the ``flops`` against a hand count, the configuration file against the
published config, a run through ``run.py`` after the look for a chip
(driver, counters, both loss terms, the comparison with the reference)
and the reference against itself under the cell's controls."""
import json
import os

import pytest

from benchmark import control
from benchmark import run as bench_run
from benchmark.flops import joyai_flash as flops
from benchmark.tests.conftest import make_cell

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "joyai-llm-flash.train-t8192"

TINY = {
    "family": "joyai_flash", "hidden_size": 64, "vocab_size": 120,
    "num_hidden_layers": 3, "first_k_dense_replace": 1,
    "intermediate_size": 96, "moe_intermediate_size": 32,
    "num_attention_heads": 4, "q_lora_rank": 48, "kv_lora_rank": 32,
    "qk_nope_head_dim": 16, "qk_rope_head_dim": 8, "v_head_dim": 16,
    "rope_theta": 32000000, "rms_norm_eps": 1e-6, "n_routed_experts": 4,
    "num_experts_per_tok": 3, "n_shared_experts": 1,
    "routed_scaling_factor": 2.5, "num_nextn_predict_layers": 1,
    "scoring_func": "sigmoid", "topk_method": "noaux_tc", "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "rope_interleave": True,
    "deployment": {"router_width": 16, "first_expert": 4},
    "assumed": {"compute_dtype": "bfloat16", "remat_policy": "ctx",
                "moe_rows_factor": 2.0, "mtp_loss_weight": 0.3,
                "optimizer": {"name": "adamw", "learning_rate": 3e-4,
                              "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
                              "weight_decay": 0.01}}}
# at the tiny size (128 tokens a step, 24 an expert) one flipped top-k
# choice moves a whole expert's leaf, so the first gradient's worst leaf
# cannot tell bfloat16 (0.06 to 0.10 on seeds 3, 4, 5) from fp8 (0.04 to
# 0.11): it holds the half batch off (0.84 and more), and the loss and
# the parameters' change hold fp8 off — bfloat16 reads 1.7e-5 to 6.6e-5
# and 0.009 to 0.013, fp8 1.5e-4 to 3.9e-4 and 0.017 to 0.024 (CPU runs,
# PR 37)
TINY_LIMITS = {"loss_gap": 1e-4, "grad_gap": 0.3, "change_gap": 0.015}
LEAF_AXES = {**{f"layers/{l}/{w}": [0] for l in (1, 2)
                for w in ("w1", "w3", "w2")},
             **{f"mtp/layer/{w}": [0] for w in ("w1", "w3", "w2")}}


def _file(*parts):
    with open(os.path.join(HERE, "..", *parts)) as f:
        return json.load(f)


@pytest.fixture
def tiny_cell():
    return make_cell("tiny-joyai-flash.train", TINY, {
        "driver": "joyai_train", "reference": "joyai_flash",
        "flops": "joyai_flash", "check": "training",
        "traffic": {"batch": 4, "seq_len": 32, "pool": 4, "check_steps": 3},
        "reference_args": {"rows": 2}, "leaf_axes": dict(LEAF_AXES),
        "limits": dict(TINY_LIMITS),
        "controls": [{"name": "fp8", "precision": "fp8"},
                     {"name": "half_batch", "fault": "half_batch"},
                     {"name": "state_unchanged",
                      "fault": "state_unchanged"}]})


def test_step_flops_against_a_hand_count():
    cfg = _file("configs", "joyai-llm-flash.json")
    traffic = _file("workloads", CELL + ".json")["traffic"]
    D, V, N, T = 2048, 16160, 2 * 8192, 8192
    mla = D * 1536 + 1536 * 32 * 192 + D * 576 + 512 * 32 * 256 + 4096 * D
    assert flops.mla_params(cfg) == mla == 26345472
    dense = 6 * mla + 3 * D * 7168 + 5 * (D * 256 + 3 * D * 768) + 2 * D * D
    assert flops.dense_matmul_params(cfg) == dense
    assert flops.expected_assignments(cfg, traffic) == 4096
    assert flops.head_rows(cfg, traffic) == N + 2 * (T - 1)
    experts = 6 * 3 * D * 768 * 4096 * 5
    scores = 3 * 6 * 2 * 32 * T * T * (192 + 128)
    assert flops.step_flops(cfg, traffic) == \
        6 * dense * N + 6 * D * V * (2 * N - 2) + experts + scores
    # the issue's reckoning, TFLOP: flash attention 24.7, the MLA
    # projections 15.5, the head's two passes 6.5, routed experts 0.6
    assert scores == pytest.approx(24.7e12, rel=5e-3)
    assert 6 * 6 * mla * N == pytest.approx(15.5e12, rel=5e-3)
    assert 6 * D * V * (2 * N - 2) == pytest.approx(6.5e12, rel=5e-3)
    assert experts == pytest.approx(0.58e12, rel=5e-3)
    assert flops.step_flops(cfg, traffic) == pytest.approx(55.1e12, rel=5e-3)
    assert flops.samples_per_step(cfg, traffic) == 2
    ops, nbytes = flops.kernel_work(cfg, traffic, "mla_attn")
    assert ops == scores
    assert nbytes == 6 * N * 32 * (6 * 192 + 5 * 128) * 2
    ops, nbytes = flops.kernel_work(cfg, traffic, "moe_experts")
    assert ops == experts
    assert nbytes == 5 * (5 * 4096 * D + 3 * 8 * 3 * D * 768) * 2
    with pytest.raises(KeyError):
        flops.kernel_work(cfg, traffic, "loss_head")


def test_the_configuration_file_is_the_published_one_but_for_reduced():
    cfg = _file("configs", "joyai-llm-flash.json")
    bench = _file("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "joyai-llm-flash")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "n_routed_experts", "vocab_size"]
    assert cfg["source"] == entry["source"]
    published = {
        "attention_bias": False, "ep_size": 1, "first_k_dense_replace": 1,
        "head_dim": 64, "hidden_act": "silu", "hidden_size": 2048,
        "intermediate_size": 7168, "kv_lora_rank": 512,
        "max_position_embeddings": 131072, "model_type": "joyai_llm_flash",
        "moe_intermediate_size": 768, "moe_layer_freq": 1, "n_group": 1,
        "n_routed_experts": 256, "n_shared_experts": 1,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts_per_tok": 8, "num_hidden_layers": 40,
        "num_key_value_heads": 32, "num_nextn_predict_layers": 1,
        "q_lora_rank": 1536, "qk_head_dim": 192, "qk_nope_head_dim": 128,
        "qk_rope_head_dim": 64, "rms_norm_eps": 1e-06,
        "rope_interleave": True, "rope_scaling": None,
        "rope_theta": 32000000, "routed_scaling_factor": 2.5,
        "scoring_func": "sigmoid", "tie_word_embeddings": False,
        "topk_group": 1, "topk_method": "noaux_tc", "v_head_dim": 128,
        "vocab_size": 129280}
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    # the floors: the leading dense layer and four expert layers, 8
    # routed experts, an eighth of the vocabulary; the module whole
    assert cfg["num_hidden_layers"] == cfg["first_k_dense_replace"] + 4
    assert cfg["n_routed_experts"] == 8 == cfg["deployment"]["experts_held"]
    assert cfg["vocab_size"] * 8 == 129280
    assert cfg["deployment"]["router_width"] == 256
    assert cfg["deployment"]["chips_per_layer"] * cfg["n_routed_experts"] \
        == 256


def test_the_driver_builds_the_program_s_configuration():
    from benchmark.drivers.joyai_train import model_config
    mc = model_config(_file("configs", "joyai-llm-flash.json"))
    assert (mc.n_routed_experts, mc.held, mc.num_experts_per_tok) \
        == (256, 8, 8)
    assert (mc.num_layers, mc.vocab_size, mc.first_k_dense_replace) \
        == (5, 16160, 1)
    assert (mc.rope_theta, mc.routed_scaling_factor, mc.mtp_loss_weight) \
        == (3.2e7, 2.5, 0.3)
    assert mc.moe_rows(2 * 8192) == 16384
    # 491.7 M parameters here
    import jax
    import numpy as np
    from paddle_tpu.models.joyai_flash import init_joyai_flash_params
    shapes = jax.eval_shape(
        lambda: init_joyai_flash_params(mc, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == 491697408
    with pytest.raises(NotImplementedError, match="n_group"):
        model_config(dict(TINY, n_group=8))


def test_every_held_expert_is_a_leaf_of_its_own():
    import jax
    from benchmark.checks import training
    from benchmark.references import joyai_flash as ref
    params = ref.init_params(TINY, 0)
    flat = training.flatten_norms(jax.device_get(
        training.leaf_norms(params, LEAF_AXES)))
    assert {f"mtp/layer/w2/{e}" for e in range(4)} <= set(flat)
    assert len(flat) == len(jax.tree.leaves(params)) + 9 * 3
    want = {**{f"layers/{l}/{w}": [0] for l in (1, 2, 3, 4)
               for w in ("w1", "w3", "w2")},
            **{f"mtp/layer/{w}": [0] for w in ("w1", "w3", "w2")}}
    assert _file("workloads", CELL + ".json")["leaf_axes"] == want


def test_program_agrees_with_reference(tiny_cell, cpu_devs):
    out = bench_run.run_cell(tiny_cell, 3, 0.2, 0, cpu_devs, peaks=None)
    assert out["correct"], out["checked"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["metrics"]["train_samples_per_s"]["value"] > 0
    assert out["checked"]["compiles_in_window"] == [0, 0]
    moe = out["info"]["window"]["moe"]
    assert len(moe["checked_steps"]) == 3
    for step in moe["checked_steps"] + [moe["last_step"]]:
        assert step["moe_overflow"] == 0
        assert len(step["moe_counts"]) == 3     # two layers, the module's
        assert all(len(c) == 4 for c in step["moe_counts"])
    # the two terms of every checked step's loss, whose weighted sum is
    # what the check compared
    for step, loss in zip(moe["checked_steps"],
                          out["info"]["check"]["spread"]["loss"]):
        assert step["loss_main"] > 0 and step["loss_mtp"] > 0
        assert loss < 1e-2
    assert moe["steps_with_overflow"] == 0
    json.dumps(out)


def test_the_control_and_the_faults_come_out_not_correct(tiny_cell):
    out = control.verdicts(tiny_cell, 3)
    assert set(out) == {"fp8", "half_batch", "state_unchanged"}
    for name, v in out.items():
        assert v["correct"] is False, (name, v["checked"])
    assert out["state_unchanged"]["checked"]["change_gap"][0] == \
        pytest.approx(1.0)


def test_the_cell_s_metric_files_exist_and_name_its_scopes():
    bench = _file("..", "BENCHMARK.json")
    mine = [m for m in bench["per_layer"] if m.get("workloads") == [CELL]]
    assert [m["name"] for m in mine] == [
        "mla_attn_roofline", "mla_proj_ms", "mtp_ms",
        "joyai_moe_shuffle_ms", "joyai_moe_experts_roofline",
        "joyai_unattributed_device_pct"]
    scopes = set()
    for m in mine:
        spec = _file("layer_metrics", m["name"] + ".json")
        assert m["moves"] == "train_samples_per_s"
        scopes.update(s for s in spec.get("scopes", []) if s)
    assert scopes == {"mla_q", "mla_kv", "mla_out", "mtp", "mtp_in",
                      "moe_route", "moe_dispatch", "moe_combine",
                      "shared_expert"}
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1
    assert entry["why"] == _file("workloads", CELL + ".json")["why"]
