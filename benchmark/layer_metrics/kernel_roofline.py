"""A piece of work's share of its roofline: the least time the chip could
take for it in one step (the larger of operations / peak and bytes /
bandwidth, from the configuration's shapes) over the device time per
step of the events the metric's patterns match (the union of
their intervals)."""
from benchmark.harness import trace


def read(run, spec):
    ops, nbytes = run["flops"].kernel_work(
        run["config"], run["traffic"], spec["work"])
    least = max(ops / run["peaks"]["bf16_flops_per_s"],
                nbytes / run["peaks"]["hbm_bytes_per_s"])
    shares = []
    for events in run["events"]:
        hit = trace.matching(events, spec["patterns"])
        # the union, not the sum: a matched parent (a while loop) spans
        # the children the trace lists beside it
        per_step = trace.busy_seconds(hit) / run["win"]["steps"]
        shares.append(least / run["chips"] / per_step)
    return 100.0 * sum(shares) / len(shares)
