#!/usr/bin/env python
"""CI gate for the fault-tolerant sharded PS (ISSUE 15).

Leg 1 — kill-one-shard failover: a 2-shard CTR-tower training run
(hash -> PS embedding -> cvm -> data_norm -> logistic loss) against
primary+replica pairs, with shard 0's primary running as a REAL
subprocess.  Mid-training the driver closes the replication staleness
window and SIGKILLs that primary while a ``ps.pull:fail@N`` chaos spec
injects one extra transport reset.  Asserts EXACT counts — 1 injected
reset, 2 bounded retries (1 chaos + 1 kill), 1 failover, 1 promotion —
plus bit-exact loss parity with an uninterrupted reference run and
bit-exact final embedding rows (zero lost updates), then verifies the
pools/tables wind down leak-free (no surviving non-daemon threads, no
pending replication).

Leg 2 — elastic reshard: a table checkpointed at 4 shards (verified
manifest-v2 commits) reloads onto 2 servers with row-union parity (no
dup/drop, per-row bit-exact pulls).

Wired into tools/run_all_tests.sh.
"""
import os
import signal
import socket
import subprocess
import sys
import tempfile
import textwrap
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

SERVER = """
import sys
from paddle_tpu.distributed.fleet.ps import PSServer
ep, shard_id, replicate_to = sys.argv[1], int(sys.argv[2]), sys.argv[3]
srv = PSServer(ep, shard_id=shard_id,
               replicate_to=replicate_to or None)
srv.add_sparse_table("emb", 3, seed=0)
srv.run()
"""


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def ep():
    return f"127.0.0.1:{free_port()}"


def wait_ready(endpoint, deadline=20.0):
    """Raw-socket readiness probe — deliberately NOT the failover-aware
    client path: a ping racing server startup must not promote the
    replica before the run even begins."""
    from paddle_tpu.distributed.fleet.ps import _recv_msg, _send_msg
    host, port = endpoint.rsplit(":", 1)
    t0 = time.monotonic()
    while True:
        try:
            s = socket.create_connection((host, int(port)), timeout=1.0)
            try:
                _send_msg(s, ("ping",))
                assert _recv_msg(s) == ("ok", "pong")
                return
            finally:
                s.close()
        except Exception:
            if time.monotonic() - t0 > deadline:
                raise
            time.sleep(0.05)


def ctr_tower_run(client, n_steps=6, kill_at=None, on_kill=None):
    """Deterministic CTR training loop; returns (losses, final rows)."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.distributed.fleet import DistributedEmbedding
    from paddle_tpu.distributed.fleet.ps import Communicator
    from paddle_tpu.ops import ctr

    paddle.seed(0)
    comm = Communicator(client, mode="sync")
    emb = DistributedEmbedding("emb", 100, 3, comm)
    rng = np.random.RandomState(0)
    raw_ids = rng.randint(0, 1 << 40, (8, 1)).astype(np.int64)
    buckets = ctr.hash_op(raw_ids, hash_size=100)
    flat = paddle.reshape(paddle.Tensor(buckets._data), [8])
    touched = np.unique(np.asarray(flat._data)).astype(np.int64)
    losses = []
    for step in range(n_steps):
        e = paddle.reshape(emb(paddle.reshape(flat, [8, 1])), [8, 3])
        show_clk = paddle.to_tensor(
            np.abs(rng.rand(8, 2)).astype("float32"))
        x = ctr.continuous_value_model(
            paddle.concat([show_clk, e], axis=1), show_clk, True)
        ones = paddle.to_tensor(np.ones(5, np.float32))
        x, _, _ = ctr.data_norm(x, ones * 2, ones, ones * 2)
        logit = paddle.sum(x, axis=1)
        label = paddle.to_tensor(
            (np.asarray(flat._data) % 2).astype("float32"))
        loss = paddle.mean(
            paddle.nn.functional.binary_cross_entropy_with_logits(
                logit, label))
        loss.backward()
        losses.append(float(loss))
        if kill_at is not None and step == kill_at:
            on_kill()
    rows = client.pull_sparse("emb", touched)
    comm.stop()
    return losses, rows


def counter(name):
    from paddle_tpu.profiler import metrics
    m = metrics.get(name)
    return m.value if m is not None else 0


def leg_failover():
    import numpy as np
    from paddle_tpu.distributed.fleet.ps import PSClient, PSServer
    from paddle_tpu.profiler import flight
    from paddle_tpu.utils import chaos

    # -- uninterrupted reference ------------------------------------------
    ref_eps = [ep(), ep()]
    ref_srvs = [PSServer(e, shard_id=i).start()
                for i, e in enumerate(ref_eps)]
    for s in ref_srvs:
        s.add_sparse_table("emb", 3, seed=0)
    ref_cli = PSClient(ref_eps, timeout=5.0, max_tries=2)
    ref_losses, ref_rows = ctr_tower_run(ref_cli)
    ref_cli.close()
    for s in ref_srvs:
        s.stop()

    # -- victim: shard 0's primary is a real subprocess --------------------
    p0, p1, r0, r1 = ep(), ep(), ep(), ep()
    rep_srvs = [PSServer(r0, shard_id=0, role="replica"),
                PSServer(r1, shard_id=1, role="replica")]
    pri1 = PSServer(p1, shard_id=1, replicate_to=r1)
    for s in rep_srvs + [pri1]:
        s.add_sparse_table("emb", 3, seed=0)
        s.start()
    script = os.path.join(tempfile.mkdtemp(prefix="ps_gate_"),
                          "server.py")
    with open(script, "w") as f:
        f.write(textwrap.dedent(SERVER))
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
    proc = subprocess.Popen([sys.executable, script, p0, "0", r0],
                            env=env)
    wait_ready(p0)
    wait_ready(p1)
    cli = PSClient([p0, p1], replicas=[r0, r1], timeout=5.0, max_tries=2)

    flight.clear()
    base_threads = {t for t in threading.enumerate() if not t.daemon}
    retries0 = counter("resilience.retry")
    # configure() resets per-site counters; @3 lands on a live-shard
    # pull attempt during step 1 (2 attempts per training step)
    chaos.configure("ps.pull:fail@3")

    def kill():
        # close the bounded-staleness window, then the real SIGKILL
        assert cli.flush_replication(10.0), "replication flush timed out"
        os.kill(proc.pid, signal.SIGKILL)
        proc.wait(timeout=10)

    try:
        losses, rows = ctr_tower_run(cli, kill_at=2, on_kill=kill)
    finally:
        chaos.reset()

    injected = counter("chaos.injected.ps.pull")
    failovers = counter("ps.failover")
    promotes = counter("ps.promote")
    retries = counter("resilience.retry") - retries0
    assert injected == 1, f"injected resets: {injected} != 1"
    assert failovers == 1, f"failovers: {failovers} != 1"
    assert promotes == 1, f"promotions: {promotes} != 1"
    assert retries == 2, f"bounded retries: {retries} != 2 " \
        f"(1 chaos + 1 kill-path)"
    view = cli.shard_views[0]
    assert view.promoted and view.primary == r0
    assert cli._shard_call(0, ("role",)) == "primary"
    fc = flight.counts()
    assert fc.get("ps.failover") == 1 and fc.get("ps.promote") == 1, fc
    assert losses == ref_losses, \
        f"loss trajectory diverged:\n{losses}\nvs\n{ref_losses}"
    assert np.array_equal(rows, ref_rows), "lost updates after failover"
    # promoted shard keeps serving writes
    cli.push_sparse("emb", np.arange(4, dtype=np.int64),
                    np.ones((4, 3), np.float32))

    # -- leak-free teardown ------------------------------------------------
    st = cli._shard_call(1, ("repl_stats",))
    assert st["pending"] == 0 and st["dropped"] == 0, st
    cli.close()
    for s in rep_srvs + [pri1]:
        s.stop()
    deadline = time.monotonic() + 5.0
    while time.monotonic() < deadline:
        alive = {t for t in threading.enumerate()
                 if not t.daemon} - base_threads
        if not alive:
            break
        time.sleep(0.05)
    assert not alive, f"leaked non-daemon threads: {alive}"
    return {"injected": injected, "failovers": failovers,
            "promotes": promotes, "retries": retries,
            "final_loss": losses[-1]}


def leg_reshard():
    import numpy as np
    from paddle_tpu.distributed.fleet.ps import (AdagradSGDRule, PSClient,
                                                 PSServer)

    def cluster(n):
        eps = [ep() for _ in range(n)]
        srvs = [PSServer(e, shard_id=i, n_shards=n).start()
                for i, e in enumerate(eps)]
        for s in srvs:
            s.add_sparse_table("emb", 4, rule=AdagradSGDRule(0.1),
                               seed=11)
        return eps, srvs

    root = os.path.join(tempfile.mkdtemp(prefix="ps_gate_"), "ckpt")
    keys = np.arange(128, dtype=np.int64)
    rng = np.random.RandomState(2)
    eps4, srvs4 = cluster(4)
    cli4 = PSClient(eps4, timeout=5.0)
    for _ in range(5):
        cli4.push_sparse("emb", keys, rng.randn(128, 4).astype(np.float32))
    ref = cli4.pull_sparse("emb", keys)
    cli4.save_state(root, step=5)
    cli4.close()
    for s in srvs4:
        s.stop()

    eps2, srvs2 = cluster(2)
    cli2 = PSClient(eps2, timeout=5.0)
    cli2.load_state(root, reshard_ps=2)      # verified + resharded
    out = cli2.pull_sparse("emb", keys)
    assert np.array_equal(ref, out), "resharded rows not bit-exact"
    per = [sorted(srvs2[i]._tables["emb"]._rows) for i in range(2)]
    union = sorted(k for p in per for k in p)
    assert union == sorted(keys.tolist()), "row union broken (dup/drop)"
    assert all(k % 2 == i for i, p in enumerate(per) for k in p)
    cli2.close()
    for s in srvs2:
        s.stop()
    return {"rows": len(union), "src_shards": 4, "dst_shards": 2}


def main():
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    r1 = leg_failover()
    r2 = leg_reshard()
    print(f"ps gate OK: failover leg {r1}; reshard leg {r2}")
    return 0


if __name__ == "__main__":
    sys.exit(main())
