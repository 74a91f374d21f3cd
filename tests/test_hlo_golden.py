"""Golden compiled-program checks for the distributed rewrites.

Reference parity: ``test_fleet_sharding_meta_optimizer.py`` etc. — the
reference asserts on the op sequences its meta-optimizers inject
(c_allreduce_sum, send/recv, ...).  The TPU translation: assert on the
collectives GSPMD materialises in the compiled HLO for each parallelism
axis — cheap, deviceless (CPU-mesh compile), and it pins the contract
that a given sharding config produces the right comm pattern.
"""
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.distributed.topology import build_mesh
from paddle_tpu.models import GPTConfig
from paddle_tpu.models.gpt_spmd import build_spmd_train_step

CFG = GPTConfig(vocab_size=128, hidden_size=32, num_layers=4, num_heads=2,
                max_seq_len=16, ffn_mult=2)
RS = np.random.RandomState(0)
IDS = jnp.asarray(RS.randint(0, 128, (8, 16)), jnp.int32)
LABELS = jnp.asarray(RS.randint(0, 128, (8, 16)), jnp.int32)


def _hlo(mesh, **kw):
    from jax.sharding import NamedSharding, PartitionSpec as P
    step, init = build_spmd_train_step(CFG, mesh, **kw)
    p, s = init(seed=0)
    batch = NamedSharding(mesh, P("dp" if "dp" in mesh.axis_names
                                  else None))
    ids = jax.device_put(IDS, batch)
    labels = jax.device_put(LABELS, batch)
    # ids/labels must be jit ARGUMENTS: closure constants are embedded
    # replicated and GSPMD then replicates the whole program
    return jax.jit(step).lower(p, s, ids, labels).compile().as_text()


def _count(txt, op):
    return len(re.findall(rf"\b{op}\b", txt))


def test_dp_produces_gradient_allreduce():
    txt = _hlo(build_mesh({"dp": 8}))
    assert _count(txt, "all-reduce") > 0
    # no pipeline or mp traffic on a pure-dp mesh
    assert _count(txt, "collective-permute") == 0


def test_mp_produces_partial_sum_allreduce():
    """Megatron row-parallel matmuls leave partial sums that GSPMD
    all-reduces over mp (the reference's c_allreduce_sum after
    RowParallelLinear)."""
    txt = _hlo(build_mesh({"dp": 1, "mp": 8}))
    assert _count(txt, "all-reduce") > 0


def test_pp_produces_collective_permute():
    """The ppermute pipeline lowers to collective-permute over the pp
    axis (the reference's send_v2/recv_v2 pairs)."""
    txt = _hlo(build_mesh({"dp": 2, "pp": 2, "mp": 2}),
               num_microbatches=2)
    assert _count(txt, "collective-permute") > 0


def test_1f1b_has_reverse_permutes():
    """1F1B adds the cotangent hops: the backward ppermute uses the
    reverse permutation.  In the (dp, pp, mp) = (2, 2, 2) mesh the pp
    neighbours sit at device stride 2, so the forward hop is {0,2} and
    the reverse {2,0}; no mp-neighbour ({0,1}) permute may appear — the
    per-section qkv layout leaves nothing to reshard between mp ranks."""
    txt = _hlo(build_mesh({"dp": 2, "pp": 2, "mp": 2}),
               num_microbatches=2, schedule_mode="1F1B")
    perms = re.findall(
        r"collective-permute[^\n]*source_target_pairs=\{((?:\{[^}]*\},?)*)\}",
        txt)
    assert perms, "no collective-permutes in 1F1B program"
    joined = ";".join(perms)
    assert "0,2" in joined and "2,0" in joined
    assert "0,1" not in joined and "1,0" not in joined


def test_single_device_has_no_collectives():
    txt = _hlo(build_mesh({"dp": 1}, devices=jax.devices()[:1]))
    assert _count(txt, "all-reduce") == 0
    assert _count(txt, "collective-permute") == 0
    assert _count(txt, "all-gather") == 0
