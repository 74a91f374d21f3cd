"""chip_smoke.py rehearsed at toy sizes on the CPU.

The script's ``main()`` has no CPU mode — it fails before doing any work
unless JAX reports a TPU.  Its phases are importable functions taking
sizes, so the control flow, the checks and the HTTP path are exercised
here with the pallas kernels in interpret mode (PADDLE_PALLAS_FORCE=1).
The phase rehearsals ride the slow tier (tier-1 has no seconds to spare);
the refusal without a TPU is tier-1.
"""
import json
import os
import subprocess
import sys

import pytest

import chip_smoke

TOY = dict(vocab_size=256, hidden_size=128, num_layers=2, num_heads=2,
           max_seq_len=128, ffn_mult=2)


@pytest.fixture
def force_pallas(monkeypatch):
    monkeypatch.setenv("PADDLE_PALLAS_FORCE", "1")


def test_main_refuses_without_a_tpu():
    """Under JAX_PLATFORMS=cpu the script exits non-zero in its device
    phase and prints no result line."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "chip_smoke.py"], cwd=root,
                       env=dict(os.environ, JAX_PLATFORMS="cpu"),
                       capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert "JAX found no tpu" in r.stderr
    assert '"ok"' not in r.stdout


def test_device_phase_reports_what_jax_reports():
    import jax
    info = chip_smoke.phase_device(require="cpu")
    assert info == {"platform": "cpu",
                    "kind": jax.devices()[0].device_kind,
                    "count": len(jax.devices())}
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.phase_device(require="tpu")


def test_main_ends_with_the_result_line(monkeypatch, tmp_path, capsys):
    """The last line of stdout is exactly {"ok", "device": {"platform",
    "kind", "count"}} — whoever runs the script parses that line and
    accepts no other key.  The report (phases, cache, "claim": null) is
    the line before it and the file.  Phases are stubbed: this rehearses
    main()'s own output, not the work."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "phase_device", lambda: dict(device))
    for name in ("kernels", "train", "fit", "serve"):
        monkeypatch.setattr(chip_smoke, f"phase_{name}", lambda: {"n": 1})
    monkeypatch.setattr(chip_smoke, "phase_multichip",
                        lambda: {"skipped": "1 device"})
    monkeypatch.chdir(tmp_path)
    assert not chip_smoke.main()
    lines = capsys.readouterr().out.strip().split("\n")
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-2].startswith("[report] ")
    report = json.loads(lines[-2][len("[report] "):])
    assert report["ok"] is True and report["claim"] is None
    assert list(report)[-1] == "claim"
    assert set(report["phases"]) == {"kernels", "train", "fit", "serve",
                                     "multichip"}
    with open(tmp_path / "chiprun_out" / "chip_smoke.json") as f:
        assert json.load(f) == report


@pytest.mark.slow
def test_kernels_phase_toy(force_pallas):
    out = chip_smoke.phase_kernels(
        attn_shapes=[(2, 128, "packed_small")], heads=2, head_dim=64,
        xent_shape=(128, 32, 200), ln_shapes=[(16, 128, 0.0),
                                              (20, 128, 0.2)],
        impl="interpret")
    assert out["selections"]["flash_attention.packed_small.interpret"] >= 1
    assert out["selections"]["fused_ln.interpret"] >= 2


@pytest.mark.slow
def test_train_phase_toy(force_pallas):
    # interpret-mode kernels are not Mosaic custom calls: expect none
    out = chip_smoke.phase_train(dims=TOY, batch=4, steps=3,
                                 mosaic_calls=0, dtype="float32")
    assert len(out["losses"]) == 3
    with pytest.raises(chip_smoke.SmokeFailure, match="Mosaic calls"):
        chip_smoke.phase_train(dims=TOY, batch=4, steps=1,
                               dtype="float32")


@pytest.mark.slow
def test_fit_phase_toy():
    out = chip_smoke.phase_fit(image=32, batch=8, samples=16, epochs=3,
                               classes=10, workers=2, depth=18, amp=None)
    assert out["steps"] == 6


@pytest.mark.slow
def test_serve_phase_toy():
    out = chip_smoke.phase_serve(dims=TOY, prompt_lens=(3, 20, 70),
                                 max_new=4, slots=2)
    assert out["requests"] == 3


@pytest.mark.slow
def test_multichip_phase_toy():
    out = chip_smoke.phase_multichip(dims=TOY, batch=8, microbatches=2,
                                     dtype="float32", remat="full")
    assert [m["schedule"] for m in out["meshes"]] == ["F-then-B", "1F1B"]
    json.dumps(out)
