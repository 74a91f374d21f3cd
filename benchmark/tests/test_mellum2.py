"""The Mellum2 family of the benchmark on the CPU at a tiny size: the
``flops`` against a hand count and the window's live scores against a
brute-force count, the configuration file against the published config,
a run through ``run.py`` after the look for a chip (driver, counters, the
comparison with the reference), the reference against itself under the
cell's controls, and the metric files' patterns against the step
compiled for a described v5e (skipped where none can be described)."""
import json
import os
import re

import numpy as np
import pytest

from benchmark import control
from benchmark import run as bench_run
from benchmark.flops import mellum2 as flops
from benchmark.tests.conftest import make_cell

HERE = os.path.dirname(os.path.abspath(__file__))
CELL = "mellum2-12b-a2.5b.train-t8192"
PERIOD = ["sliding_attention"] * 3 + ["full_attention"]

TINY = {
    "family": "mellum2", "hidden_size": 64, "vocab_size": 120,
    "num_hidden_layers": 4, "layer_types": PERIOD * 2,
    "mlp_layer_types": ["sparse"] * 8, "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "sliding_window": 8,
    "use_sliding_window": True, "max_window_layers": 0,
    "rope_parameters": {
        "full_attention": {
            "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
            "original_max_position_embeddings": 8192, "beta_fast": 32,
            "beta_slow": 1, "attention_factor": 1.2772588722239782},
        "sliding_attention": {"rope_type": "default",
                              "rope_theta": 500000}},
    "rms_norm_eps": 1e-6, "num_experts": 4, "num_experts_per_tok": 3,
    "moe_intermediate_size": 32, "norm_topk_prob": True,
    "hidden_act": "silu", "attention_bias": False,
    "tie_word_embeddings": False,
    "deployment": {"router_width": 16, "first_expert": 4},
    "assumed": {"compute_dtype": "bfloat16", "remat_policy": "ctx",
                "moe_rows_factor": 2.0,
                "optimizer": {"name": "adamw", "learning_rate": 3e-4,
                              "beta1": 0.9, "beta2": 0.999, "eps": 1e-8,
                              "weight_decay": 0.01}}}
# at the tiny size (128 tokens a step) one flipped top-k choice moves a
# whole expert's leaf, so the first gradient's worst leaf holds the half
# batch off (0.82 to 0.96) and the loss holds fp8 off (CPU runs, PR 42,
# seeds 3, 4, 5: the program in bfloat16 reads loss_gap 2.4e-5 to 4.1e-5,
# grad_gap 0.014 to 0.033, change_gap 0.004 to 0.012; fp8 3.5e-4 to
# 5.0e-4, 0.067 to 0.10, 0.016 to 0.019)
TINY_LIMITS = {"loss_gap": 1e-4, "grad_gap": 0.3, "change_gap": 0.015}
LEAF_AXES = {f"layers/{l}/{w}": [0] for l in range(4)
             for w in ("w1", "w3", "w2")}


def _file(*parts):
    with open(os.path.join(HERE, "..", *parts)) as f:
        return json.load(f)


@pytest.fixture
def tiny_cell():
    return make_cell("tiny-mellum2.train", TINY, {
        "driver": "mellum2_train", "reference": "mellum2",
        "flops": "mellum2", "check": "training",
        "traffic": {"batch": 4, "seq_len": 32, "pool": 4, "check_steps": 3},
        "reference_args": {"rows": 2}, "leaf_axes": dict(LEAF_AXES),
        "limits": dict(TINY_LIMITS),
        "controls": [{"name": "fp8", "precision": "fp8"},
                     {"name": "half_batch", "fault": "half_batch"},
                     {"name": "state_unchanged",
                      "fault": "state_unchanged"}]})


@pytest.mark.parametrize("T,W", [(32, 8), (40, 7), (16, 16), (12, 30),
                                 (8192, 1024)])
def test_window_scores_against_a_brute_force_count(T, W):
    if T > 1000:        # the cell's: counted by rows, not by pairs
        want = sum(min(i + 1, W) for i in range(T))
    else:
        i, j = np.meshgrid(np.arange(T), np.arange(T), indexing="ij")
        want = int(((i - j >= 0) & (i - j < W)).sum())
    assert flops.window_scores(T, W) == want


def test_step_flops_against_a_hand_count():
    cfg = _file("configs", "mellum2-12b-a2.5b.json")
    traffic = _file("workloads", CELL + ".json")["traffic"]
    D, V, N, T, W = 2304, 12288, 4 * 8192, 8192, 1024
    layer = 2 * D * 4096 + 2 * D * 512 + D * 64
    assert flops.dense_matmul_params(cfg) == 4 * layer + D * V
    assert flops.expected_assignments(cfg, traffic) == 32768
    experts = 6 * 3 * D * 896 * 32768 * 4
    full = 12 * T * T // 2 * 4096 * 4
    live = W * (W + 1) // 2 + (T - W) * W
    assert live == 7864832          # 23.4 % of a causal row's 33.55 M
    window = 3 * 12 * live * 4096 * 4
    assert flops.step_flops(cfg, traffic) == \
        6 * (4 * layer + D * V) * N + experts + full + window
    # the issue's reckoning, TFLOP: matmuls 27.25 (projections, routers,
    # the experts' expectation, the head), full attention 6.60, window
    # attention 4.64, the step 38.5
    assert 6 * (4 * layer + D * V) * N + experts == pytest.approx(
        27.25e12, rel=5e-3)
    assert full == pytest.approx(6.60e12, rel=5e-3)
    assert window == pytest.approx(4.64e12, rel=5e-3)
    assert flops.step_flops(cfg, traffic) == pytest.approx(38.5e12,
                                                           rel=5e-3)
    assert flops.samples_per_step(cfg, traffic) == 4
    attn_bytes = 6 * N * (4096 + 512) * 2
    assert flops.kernel_work(cfg, traffic, "window_attention") == \
        (window, 3 * attn_bytes)
    assert flops.kernel_work(cfg, traffic, "attention") == (full, attn_bytes)
    ops, nbytes = flops.kernel_work(cfg, traffic, "moe_experts")
    assert ops == experts
    assert nbytes == 4 * (5 * 32768 * D + 3 * 8 * 3 * D * 896) * 2
    with pytest.raises(KeyError):
        flops.kernel_work(cfg, traffic, "loss_head")


def test_the_configuration_file_is_the_published_one_but_for_reduced():
    """Every key of the published config.json (the catalog's row) with its
    value, but the three ``reduced`` ones, whose published values the
    file states; the floors of a model_config cut."""
    cfg = _file("configs", "mellum2-12b-a2.5b.json")
    bench = _file("..", "BENCHMARK.json")
    entry = next(c for c in bench["configs"]
                 if c["name"] == "mellum2-12b-a2.5b")
    assert entry["reduced"] == cfg["reduced"] == [
        "num_hidden_layers", "num_experts", "vocab_size"]
    assert cfg["source"] == entry["source"]
    published = {
        "attention_bias": False, "head_dim": 128, "hidden_act": "silu",
        "hidden_size": 2304, "intermediate_size": 7168,
        "layer_types": PERIOD * 7, "mlp_layer_types": ["sparse"] * 28,
        "max_position_embeddings": 131072, "max_window_layers": 0,
        "model_type": "mellum", "moe_intermediate_size": 896,
        "norm_topk_prob": True, "num_attention_heads": 32,
        "num_experts": 64, "num_experts_per_tok": 8,
        "num_hidden_layers": 28, "num_key_value_heads": 4,
        "rms_norm_eps": 1e-06,
        "rope_parameters": {
            "full_attention": {
                "rope_type": "yarn", "rope_theta": 500000, "factor": 16,
                "original_max_position_embeddings": 8192, "beta_fast": 32,
                "beta_slow": 1, "attention_factor": 1.2772588722239782},
            "sliding_attention": {"rope_type": "default",
                                  "rope_theta": 500000}},
        "sliding_window": 1024, "tie_word_embeddings": False,
        "vocab_size": 98304, "use_sliding_window": True}
    for key, value in published.items():
        if key in cfg["reduced"]:
            assert cfg["published"][key] == value and cfg[key] != value
        else:
            assert cfg[key] == value, key
    # the floors: one whole period and four layers, 8 routed experts, an
    # eighth of the vocabulary
    assert cfg["layer_types"][:cfg["num_hidden_layers"]] == PERIOD
    assert cfg["num_experts"] == 8 == cfg["deployment"]["experts_held"]
    assert cfg["vocab_size"] * 8 == 98304
    assert cfg["deployment"]["router_width"] == 64
    assert cfg["deployment"]["chips_per_layer"] * cfg["num_experts"] == 64


def test_the_driver_builds_the_program_s_configuration():
    import jax
    from benchmark.drivers import lfm2_train
    from benchmark.drivers.mellum2_train import model_config, setup
    from paddle_tpu.models.mellum2 import init_mellum2_params
    mc = model_config(_file("configs", "mellum2-12b-a2.5b.json"))
    assert (mc.num_experts, mc.held, mc.num_experts_per_tok) == (64, 8, 8)
    assert (mc.num_layers, mc.vocab_size, mc.sliding_window) \
        == (4, 12288, 1024)
    assert [mc.window_of(l) for l in range(4)] == [1024] * 3 + [None]
    assert mc.yarn == (16.0, 8192, 32.0, 1.0)
    assert mc.moe_rows(4 * 8192) == 131072
    shapes = jax.eval_shape(
        lambda: init_mellum2_params(mc, jax.random.PRNGKey(0)))
    assert sum(int(np.prod(s.shape)) for s in jax.tree.leaves(shapes)) \
        == 340349184
    # lfm2_train's setup, run over this driver's model_config
    assert setup.__code__ is lfm2_train.setup.__code__
    assert setup.__globals__["model_config"] is model_config
    with pytest.raises(NotImplementedError, match="hidden_act"):
        model_config(dict(TINY, hidden_act="gelu"))


def test_every_held_expert_is_a_leaf_of_its_own():
    import jax
    from benchmark.checks import training
    from benchmark.references import mellum2 as ref
    params = ref.init_params(TINY, 0)
    flat = training.flatten_norms(jax.device_get(
        training.leaf_norms(params, LEAF_AXES)))
    assert {f"layers/3/w2/{e}" for e in range(4)} <= set(flat)
    assert len(flat) == len(jax.tree.leaves(params)) + 12 * 3
    assert _file("workloads", CELL + ".json")["leaf_axes"] == {
        f"layers/{l}/{w}": [0] for l in range(4) for w in ("w1", "w3", "w2")}


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
        assert len(step["moe_counts"]) == 4
        assert all(len(c) == 4 for c in step["moe_counts"])
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
        "swa_attn_roofline", "mellum_full_attn_roofline",
        "mellum_attn_proj_ms", "mellum_moe_shuffle_ms",
        "mellum_moe_experts_roofline", "mellum_unattributed_device_pct"]
    scopes = set()
    for m in mine:
        spec = _file("layer_metrics", m["name"] + ".json")
        assert m["moves"] == "train_samples_per_s"
        scopes.update(s for s in spec.get("scopes", []) if s)
    assert scopes == {"mellum_qkv", "mellum_out", "moe_route",
                      "moe_dispatch", "moe_combine"}
    entry = next(w for w in bench["workloads"] if w["name"] == CELL)
    assert entry["chips"] == 1
    assert entry["why"] == _file("workloads", CELL + ".json")["why"]
    assert len(entry["why"]) <= 200


@pytest.fixture(scope="module")
def compiled_step_text():
    """The cell's step (two layers: a window layer and the full one) at
    the cell's shapes, compiled for a described v5e."""
    import sys
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:   # noqa: BLE001 — no libtpu / no such topology
        pytest.skip(f"no device-less TPU topology here: {e!r}")
    from benchmark.drivers.mellum2_train import model_config
    from paddle_tpu.models.gpt_spmd import build_spmd_train_step
    from paddle_tpu.ops import pallas
    fa = sys.modules["paddle_tpu.ops.pallas.flash_attention"]
    mp = pytest.MonkeyPatch()
    for mod in (pallas, fa):
        mp.setattr(mod, "on_tpu", lambda: True)
    mp.setattr(fa, "_vmem_capacity", lambda: 128 << 20)
    try:
        cfg = _file("configs", "mellum2-12b-a2.5b.json")
        cfg = dict(cfg, num_hidden_layers=2,
                   layer_types=["sliding_attention", "full_attention"])
        mc = model_config(cfg)
        mesh = Mesh(np.asarray(list(topo.devices)[:1]), ("dp",))
        step, _ = build_spmd_train_step(mc, mesh, compute_dtype=jnp.bfloat16,
                                        remat_policy="ctx")
        parts = mc.spmd_parts(mesh)
        params = jax.tree.map(
            lambda s, ns: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                               sharding=ns),
            jax.eval_shape(parts.init, jax.random.PRNGKey(0)),
            parts.shardings)
        rep = NamedSharding(mesh, P())
        opt = {"m": params, "v": params,
               "step": jax.ShapeDtypeStruct((), jnp.int32, sharding=rep)}
        ids = jax.ShapeDtypeStruct((4, 8192), jnp.int32, sharding=rep)
        return step.lower(params, opt, ids, ids).compile().as_text()
    finally:
        mp.undo()


def test_the_patterns_select_window_and_full_calls_apart(compiled_step_text):
    ins = [re.sub(r"^\s*(ROOT )?", "", line)
           for line in compiled_step_text.splitlines() if " = " in line]
    mosaic = [i for i in ins if 'custom_call_target="tpu_custom_call"' in i]

    def hits(metric):
        rx = [re.compile(p)
              for p in _file("layer_metrics", metric + ".json")["patterns"]]
        return [c for c in mosaic if any(r.search(c) for r in rx)]

    window, full = hits("swa_attn_roofline"), hits(
        "mellum_full_attn_roofline")
    assert len(window) == 2 and len(full) == 2
    assert not set(window) & set(full)
    assert all("bf16[128,8192,128]" in c for c in window + full)
    experts = hits("mellum_moe_experts_roofline")
    head = hits("mellum2_loss_head_events")
    assert len(head) == 1
    assert len(window + full + experts + head) == len(mosaic)
    loop = re.compile(_file("layer_metrics", "mellum2_loss_head_events.json")
                      ["patterns"][1])
    assert sum(bool(loop.search(i)) for i in ins) == 1
