#!/usr/bin/env python
"""CI chaos gate: run a small supervised ``Model.fit`` under a FIXED
chaos spec — one injected checkpoint-write failure plus delayed store
RPCs — SIGKILL the worker mid-run, and assert that training completes
with the expected ``chaos.injected`` / ``ckpt.write_fail`` /
``launch.restarts`` counts.

This is the end-to-end fault-tolerance smoke: supervisor relaunch,
verified checkpoint resume, chaos determinism, and metrics accounting
all have to line up for it to pass.  Wired into tools/run_all_tests.sh.
"""
import json
import os
import subprocess
import sys
import tempfile
import textwrap

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

CHAOS_SPEC = "ckpt.write:fail@2;store.rpc:delay=0.02@2-3"

TRAINER = """
import json, os, signal
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.distributed import checkpoint as ckpt
from paddle_tpu.hapi.callbacks import Callback
from paddle_tpu.profiler import metrics

gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
work = os.environ["CHAOS_GATE_DIR"]

paddle.seed(0)
net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.Tanh(),
                           paddle.nn.Linear(8, 1))
model = paddle.Model(net)
opt = paddle.optimizer.Adam(1e-2, parameters=net.parameters())
model.prepare(opt, paddle.nn.MSELoss())


class DS(paddle.io.Dataset):
    def __getitem__(self, i):
        import time
        time.sleep(0.02)
        rng = np.random.RandomState(i)
        x = rng.rand(4).astype("float32")
        return x, (x.sum(keepdims=True) * 0.5).astype("float32")

    def __len__(self):
        return 32           # batch 4 -> 8 global steps


class Killer(Callback):
    def on_train_batch_end(self, step, logs=None):
        if gen == 0 and step == 5:
            os.kill(os.getpid(), signal.SIGKILL)


ckptr = ckpt.AsyncCheckpointer(os.path.join(work, "ckpt"), max_to_keep=3)
model.fit(DS(), batch_size=4, epochs=1, verbose=0, shuffle=False,
          checkpointer=ckptr, callbacks=[Killer()])
ckptr.close()
snap = metrics.snapshot()
with open(os.path.join(work, "metrics.json"), "w") as f:
    json.dump({"gen": gen, **{k: v for k, v in snap.items()
                              if k.startswith(("chaos.", "ckpt.",
                                               "resilience."))}}, f)
"""


def main():
    work = tempfile.mkdtemp(prefix="chaos_gate_")
    trainer = os.path.join(work, "trainer.py")
    with open(trainer, "w") as f:
        f.write(textwrap.dedent(TRAINER))
    report = os.path.join(work, "report.json")
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO,
               FLAGS_chaos_spec=CHAOS_SPEC,
               CHAOS_GATE_DIR=work,
               PADDLE_HEARTBEAT_INTERVAL="0.05",
               PADDLE_SUPERVISE_REPORT=report)
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--supervise", "--nproc", "1", "--max_restarts", "2", trainer],
        env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    if r.returncode != 0:
        print(r.stdout[-3000:], file=sys.stderr)
        print(r.stderr[-3000:], file=sys.stderr)
        raise SystemExit(f"chaos gate: supervised launch failed "
                         f"(rc={r.returncode})")

    rep = json.load(open(report))
    assert rep["kind"] == "done", rep
    assert rep["restarts"] == 1, \
        f"expected exactly 1 supervised relaunch, got {rep}"
    assert rep["restarts_metric"] == 1, rep

    snap = json.load(open(os.path.join(work, "metrics.json")))
    assert snap["gen"] == 1, snap               # the resumed generation
    # deterministic schedule: per process, the 2nd checkpoint commit
    # fails and store RPCs 2-3 are delayed
    assert snap.get("chaos.injected.ckpt.write") == 1, snap
    assert snap.get("ckpt.write_fail") == 1, snap
    assert snap.get("chaos.injected.store.rpc", 0) >= 1, snap
    assert snap.get("chaos.injected", 0) == \
        snap.get("chaos.injected.ckpt.write", 0) + \
        snap.get("chaos.injected.store.rpc", 0), snap
    print(f"chaos gate OK: restarts={rep['restarts']}, "
          f"injected={snap['chaos.injected']} "
          f"(ckpt.write={snap['chaos.injected.ckpt.write']}, "
          f"store.rpc={snap['chaos.injected.store.rpc']}), "
          f"write_fail={snap['ckpt.write_fail']}")


if __name__ == "__main__":
    main()
