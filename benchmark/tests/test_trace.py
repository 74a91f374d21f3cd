"""The trace reduction on a synthetic trace."""
import pytest

from benchmark.harness import trace

MS = 1e6   # ns

# a 10 ms window: two overlapping ops, a gap, a parent with two children
EVENTS = [
    ("%fusion.1 = f32[8] fusion(...)", 0 * MS, 2 * MS),
    ("%fusion.2 = f32[8] fusion(...)", 1 * MS, 2 * MS),      # overlaps .1
    ("%while.3 = (s32[]) while(...)", 5 * MS, 4 * MS),       # parent
    ("%custom-call.4 = bf16[4] custom-call(...)", 5 * MS, 1 * MS),
    ("%fusion.5 = f32[8] fusion(...)", 7 * MS, 2 * MS),
]


def test_busy_is_the_union_not_the_sum():
    assert trace.busy_seconds(EVENTS) == pytest.approx(7e-3)
    assert sum(d for _n, _s, d in EVENTS) / 1e9 == pytest.approx(11e-3)
    from benchmark.layer_metrics import device_idle
    run = {"busy_s": trace.busy_seconds(EVENTS), "window_s": 10e-3}
    assert device_idle.read(run, {}) == pytest.approx(30.0)


def test_clip_cuts_events_to_the_window():
    cut = trace.clip(EVENTS, 1.5 * MS, 6 * MS)
    assert trace.busy_seconds(cut) == pytest.approx(2.5e-3)
    assert all(s >= 1.5 * MS and s + d <= 6 * MS for _n, s, d in cut)


def test_a_pattern_that_matches_nothing_raises():
    assert len(trace.matching(EVENTS, [r"^%while"])) == 1
    assert len(trace.matching(EVENTS, [r"custom-call\(", r"^%while"])) == 2
    with pytest.raises(LookupError):
        trace.matching(EVENTS, [r"flash_attention"])


def test_parents_are_left_out_of_the_top_operations():
    names = [e[0] for e in trace.leaf_events(EVENTS)]
    assert not any(n.startswith("%while") for n in names)
    top = dict(trace.top_ops(EVENTS))
    assert top["fusion"] == pytest.approx(6e-3)
    assert top["custom-call"] == pytest.approx(1e-3)
    assert "while" not in top
    assert trace.short_name("%bitcast_fusion.12.3 = x") == "bitcast_fusion"


def test_idle_gaps_go_to_the_innermost_host_span():
    spans = [("bench.outer", 0, 10 * MS), ("bench.wait", 3 * MS, 1.5 * MS)]
    gaps = dict(trace.idle_gaps(EVENTS, spans, 0, 10 * MS))
    assert gaps["bench.wait"] == pytest.approx(2e-3)     # 3..5 ms
    assert gaps["bench.outer"] == pytest.approx(1e-3)    # 9..10 ms
    none = dict(trace.idle_gaps(EVENTS, [], 0, 10 * MS))
    assert none["unattributed"] == pytest.approx(3e-3)


def test_kernel_roofline_reads_the_union_of_the_matched_events():
    from benchmark.layer_metrics import kernel_roofline

    class Flops:
        @staticmethod
        def kernel_work(config, traffic, work):
            return 2e9, 1e3          # 2 GFLOP a step, no bytes to speak of

    run = {"flops": Flops, "config": {}, "traffic": {},
           "win": {"steps": 1},
           "chips": 1, "events": [EVENTS],
           "peaks": {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e12}}
    # the while (4 ms) spans its child: 2 ms at peak over 4 ms = 50 %
    spec = {"work": "x", "patterns": [r"^%while", r"^%custom-call"]}
    assert kernel_roofline.read(run, spec) == pytest.approx(50.0)
    with pytest.raises(LookupError):
        kernel_roofline.read(run, {"work": "x", "patterns": ["nothing"]})


def test_mfu_reads_the_rate_of_the_traced_window():
    from benchmark.flops import gpt
    from benchmark.layer_metrics import mfu
    cfg = {"n_embd": 1024, "n_layer": 24, "n_head": 16, "vocab_size": 50257}
    traffic = {"batch": 32, "seq_len": 1024}
    run = {"flops": gpt, "config": cfg, "traffic": traffic, "chips": 1,
           "win": {"samples": 448, "window_s": 10.9},
           "peaks": {"bf16_flops_per_s": 197e12}}
    per_sample = gpt.step_flops(cfg, traffic) / 32
    assert mfu.read(run, {}) == pytest.approx(
        100 * per_sample * 448 / 10.9 / 197e12)
