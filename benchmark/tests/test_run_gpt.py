"""The rest of a run after the look for a chip, on a tiny GPT on the CPU:
the reference against the program, the control, and the timed path broken
underneath (``correct`` has to come out false)."""
import pytest

from benchmark import control
from benchmark import run as bench_run

# at the tiny size the program reads 0.005-0.009 and 0.009-0.014 over four
# seeds, the fp8 control 0.028-0.056 on the gradient (CPU runs, PR 27)
TINY_LIMITS = {"grad_gap": 0.018, "change_gap": 0.06}


def _run(cell, devs, seed=3):
    cell.cell["limits"] = dict(TINY_LIMITS)
    return bench_run.run_cell(cell, seed, 0.2, 0, devs, peaks=None)


def test_program_agrees_with_reference(tiny_gpt_cell, cpu_devs):
    out = _run(tiny_gpt_cell, cpu_devs)
    assert out["correct"], out["checked"]
    assert out["failed"] == 0 and out["attempted"] >= 1
    assert out["metrics"]["train_samples_per_s"]["value"] > 0
    assert out["metrics"]["setup_s"]["value"] > 0
    assert list(out)[-1] == "checked"
    assert out["checked"]["compiles_in_window"] == [0, 0]


def _break_step(monkeypatch, wrap):
    from paddle_tpu.models import gpt_spmd
    build = gpt_spmd.build_spmd_train_step

    def broken(*a, **kw):
        step, init_fn = build(*a, **kw)
        return wrap(step), init_fn
    monkeypatch.setattr(gpt_spmd, "build_spmd_train_step", broken)


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tiny_gpt_cell, cpu_devs, monkeypatch):
    def wrap(step):
        def unchanged(params, opt_state, ids, labels):
            import jax
            keep = jax.tree.map(lambda x: x + 0, (params, opt_state))
            loss, _p, _o = step(params, opt_state, ids, labels)
            return (loss, *keep)
        return unchanged
    _break_step(monkeypatch, wrap)
    out = _run(tiny_gpt_cell, cpu_devs)
    assert not out["correct"]
    assert out["checked"]["grad_gap"][0] == pytest.approx(1.0)
    assert out["checked"]["change_gap"][0] == pytest.approx(1.0)


def test_half_of_the_batch_left_out_is_not_correct(
        tiny_gpt_cell, cpu_devs, monkeypatch):
    def wrap(step):
        def half(params, opt_state, ids, labels):
            n = ids.shape[0] // 2
            return step(params, opt_state, ids[:n], labels[:n])
        return half
    _break_step(monkeypatch, wrap)
    out = _run(tiny_gpt_cell, cpu_devs)
    assert not out["correct"]
    assert out["checked"]["grad_gap"][0] > 3 * TINY_LIMITS["grad_gap"]


def test_the_control_and_the_faults_come_out_not_correct(tiny_gpt_cell):
    """Through the harness's own comparison and the cell's limits, as
    ``control.py`` runs them at the cell's size."""
    tiny_gpt_cell.cell["limits"] = dict(TINY_LIMITS)
    out = control.verdicts(tiny_gpt_cell, 3)
    assert set(out) == {"fp8", "half_batch", "state_unchanged"}
    for name, v in out.items():
        assert v["correct"] is False, (name, v["checked"])
    assert out["fp8"]["checked"]["grad_gap"][0] > TINY_LIMITS["grad_gap"]
    assert out["state_unchanged"]["checked"]["grad_gap"][0] == \
        pytest.approx(1.0)
    assert out["state_unchanged"]["checked"]["change_gap"][0] == \
        pytest.approx(1.0)


def test_the_reference_in_the_programs_precision_is_correct(tiny_gpt_cell):
    """bfloat16 is what the configuration states: the same comparison
    that fails fp8 has to pass it, at a third of fp8's reading or less."""
    tiny_gpt_cell.cell["limits"] = dict(TINY_LIMITS)
    tiny_gpt_cell.cell["controls"] = [
        {"name": "bfloat16", "precision": "bfloat16"},
        {"name": "fp8", "precision": "fp8"}]
    out = control.verdicts(tiny_gpt_cell, 3)
    assert out["bfloat16"]["correct"] is True, out["bfloat16"]["checked"]
    assert out["fp8"]["checked"]["grad_gap"][0] > \
        3 * out["bfloat16"]["checked"]["grad_gap"][0]


def test_control_exits_nonzero_when_a_control_passes(
        tiny_gpt_cell, monkeypatch, capsys):
    monkeypatch.setattr(bench_run, "load_cell", lambda name: tiny_gpt_cell)
    monkeypatch.setattr(bench_run, "require_chips", lambda chips: None)
    tiny_gpt_cell.cell["limits"] = dict(TINY_LIMITS)
    tiny_gpt_cell.cell["controls"] = [{"name": "fp8", "precision": "fp8"}]
    assert control.main(["--workload", "x", "--seeds", "3"]) == 0
    assert "fp8: correct = False" in capsys.readouterr().err
    tiny_gpt_cell.cell["limits"] = {"grad_gap": 1.0, "change_gap": 1.0}
    assert control.main(["--workload", "x", "--seeds", "3"]) == 1
