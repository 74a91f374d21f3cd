"""Whole step's share of the chip's peak: model operations per sample
(forward + backward, nothing recomputed counted) x samples per second of
the traced window / (chips x peak)."""


def read(run, spec):
    per_sample = run["flops"].step_flops(run["config"], run["traffic"]) \
        / run["flops"].samples_per_step(run["config"], run["traffic"])
    rate = run["win"]["samples"] / run["win"]["window_s"]
    return 100.0 * per_sample * rate / (
        run["chips"] * run["peaks"]["bf16_flops_per_s"])
