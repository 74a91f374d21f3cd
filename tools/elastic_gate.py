#!/usr/bin/env python
"""CI elastic gate: degrade-and-continue, end to end, on CPU.

Leg 1 — elastic relaunch at the surviving world size: launch 4
supervised workers (``--supervise --np 2:4``), SIGKILL one mid-step,
and assert the gang re-forms at world 3 via a rendezvous round, resumes
from the newest intact checkpoint (not step 0), reshards a DP-sharded
optimizer-state tree saved on the old 4-way mesh onto the surviving
3-way mesh bit-exactly, spends ZERO restart budget (shrinks are
degradation, not failure), and finishes with the final loss matching an
uninterrupted single-process reference run.  Exact ``launch.restarts``
/ rendezvous-round counts are pinned.

Leg 2 — straggler detection + eviction: 2 workers with ``host.slow``
armed on rank 1 (deterministic chaos delay each step) under
``--evict_stragglers``; the supervisor must flag rank 1 at exactly
``FLAGS_straggler_patience`` strikes, evict it via a rendezvous
denylist entry, and re-form at world 1 to completion.

Wired into tools/run_all_tests.sh.
"""
import json
import os
import subprocess
import sys
import tempfile
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:         # the in-process reference run imports
    sys.path.insert(0, REPO)     # the framework from the source tree
os.environ.setdefault("JAX_PLATFORMS", "cpu")

ELASTIC_TRAINER = """
import json, os, signal
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.distributed.parallel import mesh_for_world
from paddle_tpu.hapi.callbacks import Callback

rank = os.environ["PADDLE_TRAINER_ID"]
world = int(os.environ["PADDLE_TRAINERS_NUM"])
gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
work = os.environ["ELASTIC_GATE_DIR"]

with open(os.path.join(work, f"world_g{gen}_r{rank}"), "w") as f:
    f.write(str(world))

# cross-world resharding of DP-sharded state, inside the degraded gang:
# generation 0 saves a ZeRO-style sharded tree on the 4-way local mesh;
# the surviving generation restores it onto its 3-way mesh and demands
# bit parity.
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
shard_path = os.path.join(work, "sharded_opt")
if rank == "0" and gen == 0:
    mesh = mesh_for_world(4)
    tree = {"m": jax.device_put(jnp.arange(12.0) * 0.5,
                                NamedSharding(mesh, P("dp"))),
            "v": jax.device_put(jnp.arange(24.0).reshape(12, 2),
                                NamedSharding(mesh, P("dp")))}
    ckpt.save_state(shard_path, tree, step=0)
if rank == "0" and gen == 1:
    mesh = mesh_for_world(world)
    back = ckpt.load_state(shard_path, reshard_mesh=mesh, verify=True)
    np.testing.assert_array_equal(np.asarray(back["m"]),
                                  np.arange(12.0) * 0.5)
    np.testing.assert_array_equal(np.asarray(back["v"]),
                                  np.arange(24.0).reshape(12, 2))
    assert back["m"].sharding.spec == P("dp")
    assert len(back["m"].sharding.mesh.devices.flat) == world
    with open(os.path.join(work, "reshard_ok"), "w") as f:
        f.write("1")

paddle.seed(0)
net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.Tanh(),
                           paddle.nn.Linear(8, 1))
model = paddle.Model(net)
opt = paddle.optimizer.Adam(1e-2, parameters=net.parameters())
model.prepare(opt, paddle.nn.MSELoss())


class DS(paddle.io.Dataset):
    def __getitem__(self, i):
        import time
        time.sleep(0.1)      # pace steps so async commits land between
        rng = np.random.RandomState(i)
        x = rng.rand(4).astype("float32")
        return x, (x.sum(keepdims=True) * 0.5).astype("float32")

    def __len__(self):
        return 40            # batch 4 -> 10 global steps


class Chronicle(paddle.hapi.callbacks.Callback):
    def on_train_batch_end(self, step, logs=None):
        if rank == "0":
            with open(os.path.join(work, "losses.jsonl"), "a") as f:
                f.write(json.dumps({"step": step, "gen": gen,
                                    "loss": float(logs["loss"])}) + "\\n")
        if rank == "1" and gen == 0 and step >= 2:
            # die MID-step-stream, but only once the chronicler rank
            # has demonstrably trained past step 5 (its per-step
            # commits then exist to resume from) — rank startup cost is
            # not uniform, so a fixed kill step can fire before slower
            # ranks have even begun.  Block here until rank 0 gets
            # there (the watchdog allows 60s of stall).
            import time
            for _ in range(400):
                try:
                    with open(os.path.join(work, "losses.jsonl")) as f:
                        rows = [json.loads(line) for line in f]
                    if rows and max(r_["step"] for r_ in rows) >= 5:
                        os.kill(os.getpid(), signal.SIGKILL)  # lost host
                except OSError:
                    pass
                time.sleep(0.1)


# pay orbax/tensorstore's first-write init (~2s) OUTSIDE the step
# stream so per-step async commits land at their steady cadence and the
# kill finds intact checkpoints to resume from
ckpt.save_state(os.path.join(work, f"warmup_{rank}_g{gen}"),
                {"x": np.zeros(2, np.float32)})

ckptr = ckpt.AsyncCheckpointer(os.path.join(work, f"ckpt_{rank}"),
                               max_to_keep=3)
model.fit(DS(), batch_size=4, epochs=1, verbose=0, shuffle=False,
          checkpointer=ckptr, callbacks=[Chronicle()])
ckptr.close()
"""

STRAGGLER_TRAINER = """
import os
import numpy as np
import paddle_tpu as paddle

rank = os.environ["PADDLE_TRAINER_ID"]
gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
work = os.environ["ELASTIC_GATE_DIR"]

if rank == "1" and gen == 0:
    # deterministic straggler: every step of THIS rank pays the
    # host.slow delay in the fit loop
    paddle.set_flags({"FLAGS_chaos_spec": "host.slow:delay=0.4"})

paddle.seed(0)
net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.Tanh(),
                           paddle.nn.Linear(8, 1))
model = paddle.Model(net)
opt = paddle.optimizer.Adam(1e-2, parameters=net.parameters())
model.prepare(opt, paddle.nn.MSELoss())


class DS(paddle.io.Dataset):
    def __getitem__(self, i):
        import time
        time.sleep(0.05)     # keep the healthy rank busy past eviction
        rng = np.random.RandomState(i)
        x = rng.rand(4).astype("float32")
        return x, (x.sum(keepdims=True) * 0.5).astype("float32")

    def __len__(self):
        return 60            # batch 4 -> 15 steps


model.fit(DS(), batch_size=4, epochs=1, verbose=0, shuffle=False,
          prefetch_to_device=0)
with open(os.path.join(work, f"done_g{gen}_r{rank}"), "w") as f:
    f.write("1")
"""


def _run_leg(work, trainer_body, launch_args, extra_env):
    trainer = os.path.join(work, "trainer.py")
    with open(trainer, "w") as f:
        f.write(textwrap.dedent(trainer_body))
    report = os.path.join(work, "report.json")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO,
               ELASTIC_GATE_DIR=work,
               PADDLE_HEARTBEAT_INTERVAL="0.05",
               PADDLE_SUPERVISE_REPORT=report)
    env.update(extra_env)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--supervise", *launch_args, trainer],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        print(r.stdout[-3000:], file=sys.stderr)
        print(r.stderr[-3000:], file=sys.stderr)
        raise SystemExit(f"elastic gate: launch failed rc={r.returncode}")
    return json.load(open(report)), r


def leg_elastic_relaunch():
    work = tempfile.mkdtemp(prefix="elastic_gate_")
    rep, r = _run_leg(
        work, ELASTIC_TRAINER,
        ["--nproc", "4", "--np", "2:4", "--max_restarts", "2",
         "--devices_per_proc", "4"], {})

    # exact relaunch accounting: ONE shrink-relaunch, zero budget spent
    assert rep["kind"] == "done", rep
    assert rep["restarts"] == 0, rep          # shrink != failure
    assert rep["shrinks"] == 1, rep
    assert rep["restarts_metric"] == 1, rep   # launch.restarts counts it
    assert rep["world"] == 3, rep
    assert rep["world_history"] == [4, 3], rep
    assert rep["rendezvous_rounds"] == 2, rep  # one per gang formation
    assert rep["generation"] == 1, rep

    # the surviving generation ran at world 3 end to end
    for rnk in range(3):
        path = os.path.join(work, f"world_g1_r{rnk}")
        assert os.path.exists(path), f"missing {path}"
        assert open(path).read() == "3"
    assert not os.path.exists(os.path.join(work, "world_g1_r3"))

    # resumed from the newest intact checkpoint, with the DP-sharded
    # side tree resharded 4 -> 3 bit-exactly inside the degraded gang
    assert os.path.exists(os.path.join(work, "reshard_ok"))
    rows = [json.loads(line) for line in
            open(os.path.join(work, "losses.jsonl"))]
    final = {}
    for row in rows:
        final[row["step"]] = row["loss"]
    assert sorted(final) == list(range(10)), sorted(final)
    # the relaunched chronicler resumed from an INTACT commit, not from
    # scratch (>= 1: on a 2-core CI box 4 contending workers commit
    # slower than they step, so the newest intact step may trail the
    # kill step; the slow-tier 2-worker parity test pins >= 2)
    gen1_steps = [row["step"] for row in rows if row["gen"] == 1]
    assert gen1_steps and min(gen1_steps) >= 1, gen1_steps

    # final-loss parity vs an uninterrupted in-process reference
    import paddle_tpu as paddle
    paddle.seed(0)
    net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.Tanh(),
                               paddle.nn.Linear(8, 1))
    model = paddle.Model(net)
    opt = paddle.optimizer.Adam(1e-2, parameters=net.parameters())
    model.prepare(opt, paddle.nn.MSELoss())

    import numpy as np

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
    np.testing.assert_allclose([final[s] for s in range(10)], ref,
                               rtol=2e-4, atol=1e-6)
    print(f"elastic gate leg 1 OK: world {rep['world_history']} "
          f"shrinks={rep['shrinks']} restarts={rep['restarts']} "
          f"rendezvous={rep['rendezvous_rounds']}, resumed from "
          f"step {min(gen1_steps)}, final loss parity to "
          f"{final[9]:.6f}")


def leg_straggler_eviction():
    work = tempfile.mkdtemp(prefix="elastic_gate_straggler_")
    rep, r = _run_leg(
        work, STRAGGLER_TRAINER,
        ["--nproc", "2", "--np", "1:2", "--max_restarts", "1",
         "--evict_stragglers"],
        {"FLAGS_straggler_factor": "2.0",
         "FLAGS_straggler_patience": "2"})

    assert rep["kind"] == "done", rep
    assert rep["restarts"] == 0 and rep["shrinks"] == 1, rep
    assert rep["world"] == 1 and rep["world_history"] == [2, 1], rep
    assert len(rep["stragglers"]) == 1, rep
    s = rep["stragglers"][0]
    assert s["rank"] == "1" and s["generation"] == 0, rep
    # fires at the exact deterministic window: the patience'th strike
    assert s["strikes"] == 2, rep
    assert s["median_s"] > 2.0 * s["gang_median_s"], rep
    assert "evicting straggler rank 1" in r.stderr
    # the re-formed world-1 gang trained to completion
    assert os.path.exists(os.path.join(work, "done_g1_r0"))
    print(f"elastic gate leg 2 OK: straggler rank {s['rank']} evicted "
          f"after {s['strikes']} strikes (median {s['median_s']}s vs "
          f"gang {s['gang_median_s']}s), re-formed at world 1")


def main():
    leg_elastic_relaunch()
    leg_straggler_eviction()
    print("elastic gate OK")


if __name__ == "__main__":
    main()
