"""Loss parity between single-process and multi-process runs (reference
``tests/unittests/test_dist_base.py:1426`` check_with_place — the
reference's central distributed correctness gate: same global batch,
same model, N-proc losses must match 1-proc losses).

Here: the SPMD GPT train step over a dp mesh, run (a) in one process
with 4 virtual devices, (b) as 2 launcher-spawned processes x 2 devices
with jax.distributed — identical loss trajectories required.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TRAINER = """
import json, os, sys
import numpy as np
import jax
import paddle_tpu.distributed as dist

dist.init_parallel_env()   # no-op single-proc; jax.distributed multi-proc
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.models import GPTConfig
from paddle_tpu.models.gpt_spmd import build_spmd_train_step

cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                num_heads=2, max_seq_len=32)
mesh = build_mesh({"dp": jax.device_count()})
step, init_fn = build_spmd_train_step(cfg, mesh, learning_rate=1e-2)
params, opt = init_fn(seed=0)

rng = np.random.RandomState(0)          # same GLOBAL batch everywhere
B, T = 8, 32
ids_np = rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32)
lab_np = rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32)

sharding = NamedSharding(mesh, P("dp"))
n_proc = jax.process_count()
rank = jax.process_index()
per = B // n_proc


def place(arr):
    if n_proc == 1:
        return jax.device_put(jnp.asarray(arr), sharding)
    local = arr[rank * per:(rank + 1) * per]
    return jax.make_array_from_process_local_data(sharding,
                                                  local, arr.shape)


ids, labels = place(ids_np), place(lab_np)
losses = []
for i in range(5):
    loss, params, opt = step(params, opt, ids, labels)
    losses.append(float(loss))
if rank == 0:
    with open(os.environ["PARITY_OUT"], "w") as f:
        json.dump(losses, f)
"""


def _run(tmp_path, nproc, devices_per_proc, tag, trainer=None):
    script = tmp_path / f"trainer_{tag}.py"
    script.write_text(textwrap.dedent(trainer if trainer is not None
                                      else TRAINER))
    out = tmp_path / f"losses_{tag}.json"
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO, PARITY_OUT=str(out))
    if nproc == 1:
        env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                            f" --xla_force_host_platform_device_count="
                            f"{devices_per_proc}").strip()
        r = subprocess.run([sys.executable, str(script)], env=env,
                           capture_output=True, text=True, timeout=600)
    else:
        # free port PAIR at runtime: the launcher's coordinator binds
        # master_port - 1 (a fixed port collides across runs)
        from conftest import free_launch_port
        port = free_launch_port()
        r = subprocess.run(
            [sys.executable, "-m", "paddle_tpu.distributed.launch",
             "--nproc", str(nproc), "--devices_per_proc",
             str(devices_per_proc), "--master_port", str(port),
             str(script)],
            env=env, cwd=REPO, capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, (r.stdout[-2000:], r.stderr[-2000:])
    return json.load(open(out))


@pytest.mark.slow
def test_single_vs_multiprocess_loss_parity(tmp_path):
    single = _run(tmp_path, 1, 4, "single")
    multi = _run(tmp_path, 2, 2, "multi")
    assert len(single) == len(multi) == 5
    # same global math, different process decomposition
    np.testing.assert_allclose(single, multi, rtol=2e-4, atol=1e-5)
    # and the loss actually decreases (training, not a constant)
    assert single[-1] < single[0]


# ---------------------------------------------------------------------------
# hybrid (mp) across processes — beyond pure-dp parity
# ---------------------------------------------------------------------------
TRAINER_MP = """
import json, os, sys
import numpy as np
import jax
import paddle_tpu.distributed as dist

dist.init_parallel_env()
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.models import GPTConfig
from paddle_tpu.models.gpt_spmd import build_spmd_train_step

cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=2,
                num_heads=2, max_seq_len=32)
# tensor-parallel over every device: Megatron shardings cross the
# process boundary (qkv/ffn column/row splits + sharded vocab)
mesh = build_mesh({"dp": 1, "mp": jax.device_count()})
step, init_fn = build_spmd_train_step(cfg, mesh, learning_rate=1e-2)
params, opt = init_fn(seed=0)

rng = np.random.RandomState(0)
B, T = 8, 32
ids_np = rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32)
lab_np = rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32)

rep = NamedSharding(mesh, P())           # batch replicated under pure mp
def place(arr):
    if jax.process_count() == 1:
        return jax.device_put(jnp.asarray(arr), rep)
    return jax.make_array_from_process_local_data(rep, arr, arr.shape)

ids, labels = place(ids_np), place(lab_np)
losses = []
for i in range(5):
    loss, params, opt = step(params, opt, ids, labels)
    losses.append(float(loss))
if jax.process_index() == 0:
    with open(os.environ["PARITY_OUT"], "w") as f:
        json.dump(losses, f)
"""


@pytest.mark.slow
def test_mp_across_processes_loss_parity(tmp_path):
    """Megatron tensor parallel sharded across 2 launcher-spawned
    processes matches the single-process run (reference
    hybrid_parallel_mp_* launched tests)."""
    single = _run(tmp_path, 1, 4, "mp_single", trainer=TRAINER_MP)
    multi = _run(tmp_path, 2, 2, "mp_multi", trainer=TRAINER_MP)
    np.testing.assert_allclose(single, multi, rtol=2e-4, atol=1e-5)
    assert single[-1] < single[0]


# ---------------------------------------------------------------------------
# pipeline parallel across processes (round-3 VERDICT item 6; reference
# test_dist_base.py:1296-style subprocess runs of pipeline_mnist.py)
# ---------------------------------------------------------------------------
TRAINER_PP = """
import json, os, sys
import numpy as np
import jax
import paddle_tpu.distributed as dist

dist.init_parallel_env()
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.models import GPTConfig
from paddle_tpu.models.gpt_spmd import build_spmd_train_step

cfg = GPTConfig(vocab_size=256, hidden_size=64, num_layers=4,
                num_heads=2, max_seq_len=32)
# pipeline axis spans ALL devices (and the process boundary in the
# multi-proc run): ppermute-based micro-batch pipeline with real
# cross-process stage-to-stage sends
mesh = build_mesh({"pp": jax.device_count()})
step, init_fn = build_spmd_train_step(cfg, mesh, learning_rate=1e-2,
                                      num_microbatches=4,
                                      schedule_mode="1F1B")
params, opt = init_fn(seed=0)

rng = np.random.RandomState(0)
B, T = 8, 32
ids_np = rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32)
lab_np = rng.randint(0, cfg.vocab_size, (B, T)).astype(np.int32)

rep = NamedSharding(mesh, P())        # batch replicated; pp shards layers
def place(arr):
    if jax.process_count() == 1:
        return jax.device_put(jnp.asarray(arr), rep)
    return jax.make_array_from_process_local_data(rep, arr, arr.shape)

ids, labels = place(ids_np), place(lab_np)
losses = []
for i in range(5):
    loss, params, opt = step(params, opt, ids, labels)
    losses.append(float(loss))
if jax.process_index() == 0:
    with open(os.environ["PARITY_OUT"], "w") as f:
        json.dump(losses, f)
"""


@pytest.mark.slow
def test_pp_across_processes_loss_parity(tmp_path):
    """spmd_pipeline_1f1b sharded across 2 launcher-spawned processes
    (stage-to-stage ppermutes cross the process boundary) matches the
    single-process pipeline run.  Eager-mode PipelineParallel remains
    schedule-level only (single process) — this is the cross-process
    pipeline path."""
    single = _run(tmp_path, 1, 4, "pp_single", trainer=TRAINER_PP)
    multi = _run(tmp_path, 2, 2, "pp_multi", trainer=TRAINER_PP)
    np.testing.assert_allclose(single, multi, rtol=2e-4, atol=1e-5)
    assert single[-1] < single[0]
