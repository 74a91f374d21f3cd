"""Supervised relaunch tests (TorchElastic-style): crash detection,
hung-step watchdog, restart budget, and the acceptance gate — SIGKILL a
worker mid-step in a ``--max_restarts`` launch and require the training
outcome to match an uninterrupted run (same gate style as
``test_dist_parity.py``)."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ENV = dict(os.environ, JAX_PLATFORMS="cpu",
           PYTHONPATH=REPO)


def _launch(tmp_path, script_body, extra_args, env=None, timeout=300):
    script = tmp_path / "trainer.py"
    script.write_text(textwrap.dedent(script_body))
    report = tmp_path / "report.json"
    run_env = dict(ENV, PADDLE_SUPERVISE_REPORT=str(report))
    run_env.update(env or {})
    cmd = [sys.executable, "-m", "paddle_tpu.distributed.launch",
           "--supervise", *extra_args, str(script)]
    r = subprocess.run(cmd, env=run_env, cwd=REPO, capture_output=True,
                       text=True, timeout=timeout)
    rep = json.load(open(report)) if report.exists() else None
    return r, rep


def test_supervise_relaunch_on_crash(tmp_path):
    """A worker crash (nonzero exit) kills the gang, bumps
    PADDLE_RESTART_GENERATION, and relaunches; launch.restarts counts."""
    r, rep = _launch(tmp_path, """
        import os, sys
        gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
        if gen == 0 and os.environ["PADDLE_TRAINER_ID"] == "1":
            sys.exit(3)
        """, ["--nproc", "2", "--max_restarts", "2"])
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert rep["restarts"] == 1 and rep["restarts_metric"] == 1
    assert rep["kind"] == "done" and rep["code"] == 0
    assert rep["shrinks"] == 0 and rep["world"] == 2
    assert "supervised relaunch 1/2" in r.stderr


def test_supervise_restart_budget_exhausted(tmp_path):
    r, rep = _launch(tmp_path, """
        import sys
        sys.exit(5)
        """, ["--nproc", "1", "--max_restarts", "2"])
    assert r.returncode != 0
    assert rep["restarts"] == 2 and rep["kind"] == "crash"
    assert rep["code"] == 5


def test_supervise_watchdog_kills_hung_step(tmp_path):
    """A worker that heartbeats then stops advancing its step is a
    HANG, not a crash — the watchdog must detect it, kill the gang, and
    relaunch (reference: hung-collective detection; FLAGS_watchdog_timeout)."""
    r, rep = _launch(tmp_path, """
        import os, time
        gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
        if gen == 0:
            from paddle_tpu.distributed.fleet.elastic.manager import \\
                store_from_spec
            from paddle_tpu.distributed.launch import heartbeat_key
            store = store_from_spec(os.environ["PADDLE_SUPERVISE_STORE"])
            key = heartbeat_key(os.environ["PADDLE_SUPERVISE_JOB"], gen,
                                os.environ["PADDLE_TRAINER_ID"])
            store.put(key, "1")
            time.sleep(300)            # hung step: never advances
        """, ["--nproc", "1", "--max_restarts", "1",
              "--watchdog_timeout", "3"])
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert rep["restarts"] == 1 and rep["kind"] == "done"
    assert "watchdog" in r.stderr


@pytest.mark.slow    # tier-1 runtime budget: full e2e, run via --runslow
def test_supervise_done_worker_does_not_trip_watchdog(tmp_path):
    """A worker that heartbeats and then EXITS 0 stops advancing its
    heartbeat by definition — the watchdog must not read that as a hang
    while its gang-mates keep training."""
    r, rep = _launch(tmp_path, """
        import os, time
        from paddle_tpu.distributed.fleet.elastic.manager import \\
            store_from_spec
        from paddle_tpu.distributed.launch import heartbeat_key
        store = store_from_spec(os.environ["PADDLE_SUPERVISE_STORE"])
        rank = os.environ["PADDLE_TRAINER_ID"]
        gen = os.environ.get("PADDLE_RESTART_GENERATION", "0")
        key = heartbeat_key(os.environ["PADDLE_SUPERVISE_JOB"], gen, rank)
        store.put(key, "1")
        if rank == "1":          # keeps "training" past the watchdog
            for step in range(2, 14):
                time.sleep(0.5)
                store.put(key, str(step))
        """, ["--nproc", "2", "--max_restarts", "2",
              "--watchdog_timeout", "3"])
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert rep["restarts"] == 0 and rep["restarts_metric"] == 0
    assert rep["kind"] == "done" and rep["code"] == 0


def test_supervise_elastic_combo_needs_np_bounds(tmp_path):
    """The historical --supervise/--elastic exclusion is lifted into the
    unified elastic-supervise mode — but resizing needs explicit world
    bounds, so the combo without --np (and --evict_stragglers without
    elastic bounds) still errors with actionable messages."""
    script = tmp_path / "t.py"
    script.write_text("")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--supervise", "--elastic", str(script)],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "needs --np MIN:MAX" in r.stderr

    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--supervise", "--evict_stragglers", str(script)],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "--evict_stragglers requires" in r.stderr

    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--supervise", "--np", "4:2", str(script)],
        env=ENV, cwd=REPO, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert "MIN <= MAX" in r.stderr


# ---------------------------------------------------------------------------
# elastic supervise: degrade-and-continue at the surviving world size
# ---------------------------------------------------------------------------
WORLD_RECORDER = """
import json, os, signal, sys, time
gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
rank = os.environ["PADDLE_TRAINER_ID"]
world = os.environ["PADDLE_TRAINERS_NUM"]
with open(os.path.join(os.environ["ELASTIC_TEST_DIR"],
                       f"world_g{gen}_r{rank}"), "w") as f:
    f.write(world)
"""


def test_elastic_supervise_shrinks_on_signal_death(tmp_path):
    """Elastic supervise (--supervise --np MIN:MAX): a worker killed by
    signal reads as a LOST HOST — the supervisor runs a rendezvous
    round, denylists the slot, and re-forms one smaller WITHOUT
    consuming the restart budget (degradation is not failure)."""
    r, rep = _launch(tmp_path, WORLD_RECORDER + """
if gen == 0 and rank == "1":
    os.kill(os.getpid(), signal.SIGKILL)
""", ["--nproc", "3", "--np", "1:3", "--max_restarts", "2"],
        env={"ELASTIC_TEST_DIR": str(tmp_path)})
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert rep["kind"] == "done"
    assert rep["restarts"] == 0          # shrink spent NO budget
    assert rep["shrinks"] == 1
    assert rep["world"] == 2
    assert rep["world_history"] == [3, 2]
    assert rep["generation"] == 1
    assert rep["rendezvous_rounds"] == 2  # one per gang formation
    # the relaunched generation saw the surviving world via the env
    # contract
    for rank in ("0", "1"):
        assert (tmp_path / f"world_g1_r{rank}").read_text() == "2"
    assert not (tmp_path / "world_g1_r2").exists()
    assert "degrading to world 2" in r.stderr


def test_elastic_supervise_plain_crash_keeps_world(tmp_path):
    """A plain nonzero exit is a software crash on a healthy host: the
    elastic supervisor keeps the full world and spends the budget, same
    as fixed-world supervise."""
    r, rep = _launch(tmp_path, WORLD_RECORDER + """
if gen == 0 and rank == "0":
    sys.exit(7)
""", ["--nproc", "2", "--np", "1:2", "--max_restarts", "2"],
        env={"ELASTIC_TEST_DIR": str(tmp_path)})
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert rep["kind"] == "done"
    assert rep["restarts"] == 1 and rep["shrinks"] == 0
    assert rep["world"] == 2 and rep["world_history"] == [2, 2]


def test_elastic_supervise_shrink_below_min_uses_budget(tmp_path):
    """A lost host that would take the world below the --np floor can't
    shrink — the supervisor falls back to a same-world restart, which
    DOES consume the budget."""
    r, rep = _launch(tmp_path, WORLD_RECORDER + """
if gen == 0:
    os.kill(os.getpid(), signal.SIGKILL)
""", ["--nproc", "1", "--np", "1:1", "--max_restarts", "2"],
        env={"ELASTIC_TEST_DIR": str(tmp_path)})
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert rep["kind"] == "done"
    assert rep["restarts"] == 1 and rep["shrinks"] == 0
    assert rep["world"] == 1


@pytest.mark.slow    # tier-1 runtime budget: full e2e, run via --runslow
def test_generation_scoped_heartbeats_ignore_stale_keys(tmp_path):
    """Satellite: heartbeat keys are generation-prefixed.  A key left
    behind by generation 0 (stuck at its last step forever) must NOT
    feed generation 1's watchdog — only the current generation's prefix
    is read, and prior-generation keys are purged at relaunch."""
    r, rep = _launch(tmp_path, """
        import os, time
        from paddle_tpu.distributed.fleet.elastic.manager import \\
            store_from_spec
        from paddle_tpu.distributed.launch import heartbeat_key
        gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
        store = store_from_spec(os.environ["PADDLE_SUPERVISE_STORE"])
        job = os.environ["PADDLE_SUPERVISE_JOB"]
        rank = os.environ["PADDLE_TRAINER_ID"]
        if gen == 0:
            # beat once under g0, then crash: the stale g0 key now sits
            # in the store, permanently "stuck" at step 1
            store.put(heartbeat_key(job, 0, rank), "1")
            raise SystemExit(3)
        # generation 1 trains normally, advancing ITS OWN prefix for
        # longer than the watchdog window — if the supervisor still
        # watched the stale g0 key it would kill this healthy gang
        key = heartbeat_key(job, gen, rank)
        for step in range(1, 9):
            store.put(key, str(step))
            time.sleep(0.5)
        """, ["--nproc", "1", "--max_restarts", "3",
              "--watchdog_timeout", "2"])
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert rep["kind"] == "done"
    assert rep["restarts"] == 1, rep     # ONLY the gen-0 crash


# ---------------------------------------------------------------------------
# straggler detection and remediation
# ---------------------------------------------------------------------------
STRAGGLER_BEATS = """
import json, os, time
from paddle_tpu.distributed.fleet.elastic.manager import store_from_spec
from paddle_tpu.distributed.launch import heartbeat_key
gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
rank = os.environ["PADDLE_TRAINER_ID"]
store = store_from_spec(os.environ["PADDLE_SUPERVISE_STORE"])
key = heartbeat_key(os.environ["PADDLE_SUPERVISE_JOB"], gen, rank)
def run_beats(n, dt, pace=0.25):
    for step in range(1, n + 1):
        store.put(key, json.dumps({"step": step, "dt": dt}))
        time.sleep(pace)
"""


def test_straggler_reported_without_eviction(tmp_path):
    """A rank whose per-step wall time exceeds FLAGS_straggler_factor x
    the gang median for FLAGS_straggler_patience consecutive samples is
    REPORTED (launch.straggler metric + supervise report JSON) but the
    gang keeps running when --evict_stragglers is off."""
    r, rep = _launch(tmp_path, STRAGGLER_BEATS + """
run_beats(8, 0.5 if rank == "1" else 0.01)
""", ["--nproc", "2", "--max_restarts", "1"],
        env={"FLAGS_straggler_factor": "2.0",
             "FLAGS_straggler_patience": "2"})
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert rep["kind"] == "done"
    assert rep["restarts"] == 0 and rep["shrinks"] == 0
    assert len(rep["stragglers"]) == 1, rep
    s = rep["stragglers"][0]
    assert s["rank"] == "1" and s["generation"] == 0
    # fires at the exact deterministic sample: patience strikes, no more
    assert s["strikes"] == 2
    assert s["median_s"] > 2.0 * s["gang_median_s"]
    assert "straggler" in r.stderr


def test_straggler_evicted_reforms_without_host(tmp_path):
    """--evict_stragglers: detection is treated as a stall — the gang
    is killed and re-formed WITHOUT the straggler via a rendezvous
    denylist entry, shrinking the world (no restart budget spent)."""
    r, rep = _launch(tmp_path, STRAGGLER_BEATS + """
if gen == 0:
    run_beats(60, 0.5 if rank == "1" else 0.01)
# generation 1 (post-eviction, world 1) completes immediately
""", ["--nproc", "2", "--np", "1:2", "--max_restarts", "1",
          "--evict_stragglers"],
        env={"FLAGS_straggler_factor": "2.0",
             "FLAGS_straggler_patience": "2"})
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert rep["kind"] == "done"
    assert rep["restarts"] == 0 and rep["shrinks"] == 1
    assert rep["world"] == 1 and rep["world_history"] == [2, 1]
    assert len(rep["stragglers"]) == 1
    assert rep["stragglers"][0]["rank"] == "1"
    assert rep["stragglers"][0]["strikes"] == 2
    assert "evicting straggler rank 1" in r.stderr


# ---------------------------------------------------------------------------
# acceptance gate: gang-kill recovery parity
# ---------------------------------------------------------------------------
PARITY_TRAINER = """
import json, os, signal
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.hapi.callbacks import Callback

rank = os.environ["PADDLE_TRAINER_ID"]
gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
work = os.environ["SUP_TEST_DIR"]

paddle.seed(0)
net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.Tanh(),
                           paddle.nn.Linear(8, 1))
model = paddle.Model(net)
opt = paddle.optimizer.Adam(1e-2, parameters=net.parameters())
model.prepare(opt, paddle.nn.MSELoss())


class DS(paddle.io.Dataset):
    def __getitem__(self, i):
        import time
        time.sleep(0.02)     # pace steps so async commits land between
        rng = np.random.RandomState(i)
        x = rng.rand(4).astype("float32")
        return x, (x.sum(keepdims=True) * 0.5).astype("float32")

    def __len__(self):
        return 40       # batch 4 -> 10 global steps


class Chronicle(Callback):
    def on_train_batch_end(self, step, logs=None):
        if rank == "0":
            with open(os.path.join(work, "losses.jsonl"), "a") as f:
                f.write(json.dumps({"step": step, "gen": gen,
                                    "loss": float(logs["loss"])}) + "\\n")
        if rank == "1" and gen == 0 and step == 7:
            os.kill(os.getpid(), signal.SIGKILL)    # die MID-step-stream


ckptr = ckpt.AsyncCheckpointer(os.path.join(work, f"ckpt_{rank}"),
                               max_to_keep=3)
model.fit(DS(), batch_size=4, epochs=1, verbose=0, shuffle=False,
          checkpointer=ckptr, callbacks=[Chronicle()])
ckptr.close()
"""


@pytest.mark.slow
def test_gang_kill_recovery_parity(tmp_path):
    """SIGKILL one worker mid-step in a --max_restarts=2 supervised
    launch: the gang is killed and relaunched, workers resume from the
    latest intact checkpoint, and the final loss matches an
    uninterrupted run to 2e-4."""
    r, rep = _launch(tmp_path, PARITY_TRAINER,
                     ["--nproc", "2", "--max_restarts", "2"],
                     env={"SUP_TEST_DIR": str(tmp_path)}, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    assert rep["restarts"] == 1 and rep["kind"] == "done"

    rows = [json.loads(line) for line in
            (tmp_path / "losses.jsonl").read_text().splitlines()]
    final = {}
    for row in rows:                     # last write wins per step
        final[row["step"]] = row["loss"]
    assert sorted(final) == list(range(10)), sorted(final)
    gen1_steps = [row["step"] for row in rows if row["gen"] == 1]
    if gen1_steps:
        # the relaunched worker resumed from a checkpoint, not step 0
        assert min(gen1_steps) >= 2, gen1_steps

    # uninterrupted reference run (same seed/model/data, in-process)
    import paddle_tpu as paddle
    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.Tanh(),
                               paddle.nn.Linear(8, 1))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(1e-2, parameters=net.parameters())
    model.prepare(opt, paddle.nn.MSELoss())

    class DS(paddle.io.Dataset):
        def __getitem__(self, i):
            rng = np.random.RandomState(i)
            x = rng.rand(4).astype("float32")
            return x, (x.sum(keepdims=True) * 0.5).astype("float32")

        def __len__(self):
            return 40

    ref = []

    class Rec(paddle.hapi.callbacks.Callback):
        def on_train_batch_end(self, step, logs=None):
            ref.append(float(logs["loss"]))

    model.fit(DS(), batch_size=4, epochs=1, verbose=0, shuffle=False,
              callbacks=[Rec()])
    assert len(ref) == 10
    np.testing.assert_allclose(final[9], ref[-1], rtol=2e-4, atol=1e-6)
    # and the whole post-restart trajectory tracks the reference
    np.testing.assert_allclose([final[s] for s in range(10)], ref,
                               rtol=2e-4, atol=1e-6)
