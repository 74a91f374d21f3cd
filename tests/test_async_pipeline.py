"""Async training pipeline: io DevicePrefetcher (device-resident batch
queue, sharding-aware device_put, refetch-on-worker-death), the
sync-free lazy-loss fit loop (at most one host block per log_freq
window), the single-copy slot-buffered collate, the step-phase
breakdown (train.step.data_wait/host/device), and the persistent XLA
compilation cache flag."""
import os
import warnings

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.hapi.callbacks import Callback
from paddle_tpu.io import (DataLoader, Dataset, DevicePrefetcher,
                           default_collate_fn)
from paddle_tpu.io import _SlotCollate
from paddle_tpu.profiler import metrics, tracer
from paddle_tpu.utils import chaos, compile_cache, flags


class ArrayDS(Dataset):
    def __init__(self, n=20, dim=4):
        rng = np.random.RandomState(0)
        self.x = rng.rand(n, dim).astype("float32")
        self.y = rng.randint(0, 3, (n, 1))

    def __getitem__(self, i):
        return self.x[i], self.y[i]

    def __len__(self):
        return len(self.x)


def _np(t):
    return np.asarray(t._data if hasattr(t, "_data") else t)


# ---------------------------------------------------------------------------
# DevicePrefetcher
# ---------------------------------------------------------------------------

def test_prefetcher_matches_plain_loader():
    ref = [b for b in DataLoader(ArrayDS(), batch_size=4, shuffle=False)]
    got = [b for b in DataLoader(ArrayDS(), batch_size=4, shuffle=False,
                                 prefetch_to_device=2)]
    assert len(ref) == len(got) == 5
    for (x1, y1), (x2, y2) in zip(ref, got):
        assert np.array_equal(_np(x1), _np(x2))
        assert np.array_equal(_np(y1), _np(y2))
        assert x2._data.dtype == x1._data.dtype


def test_prefetcher_shuffle_same_rng_consumption():
    """Prefetch snapshots the sampler with the SAME single draw the
    plain iterator performs — fixed seed gives identical order."""
    np.random.seed(7)
    ref = [_np(b[0]) for b in DataLoader(ArrayDS(), batch_size=4,
                                         shuffle=True)]
    np.random.seed(7)
    got = [_np(b[0]) for b in DataLoader(ArrayDS(), batch_size=4,
                                         shuffle=True,
                                         prefetch_to_device=2)]
    assert all(np.array_equal(a, b) for a, b in zip(ref, got))


def test_prefetcher_one_shot_and_depth_bound():
    ld = DataLoader(ArrayDS(), batch_size=2, prefetch_to_device=3)
    out = list(ld)
    pf = ld._last_prefetcher
    assert len(out) == 10
    assert pf.stats["produced"] == 10
    assert pf.stats["max_depth"] <= 3
    with pytest.raises(RuntimeError, match="one-shot"):
        list(pf)
    # a fresh epoch gets a fresh stage
    assert len(list(ld)) == 10
    assert ld._last_prefetcher is not pf


def test_prefetcher_iterator_mode_nested_structures():
    batches = [{"a": np.ones((2, 3), np.float32) * i,
                "b": (np.arange(2, dtype=np.int32) + i, "tag")}
               for i in range(4)]
    got = list(DevicePrefetcher(iter(batches), depth=2))
    assert len(got) == 4
    for i, b in enumerate(got):
        import jax
        assert isinstance(b["a"], jax.Array)       # moved onto device
        assert np.array_equal(np.asarray(b["a"]),
                              np.ones((2, 3), np.float32) * i)
        assert b["b"][1] == "tag"                  # non-arrays pass through


def test_prefetcher_upstream_error_surfaces_in_order():
    def gen():
        yield np.zeros((2,), np.float32)
        raise ValueError("boom")
    pf = DevicePrefetcher(gen(), depth=2)
    it = iter(pf)
    next(it)
    with pytest.raises(ValueError, match="boom"):
        next(it)


def test_prefetcher_chaos_kill_recovered_zero_lost():
    ref = [_np(b[0]) for b in DataLoader(ArrayDS(), batch_size=4)]
    r0 = metrics.counter("io.prefetch.refetch").value
    chaos.configure("loader.worker:fail@3", seed=0)
    try:
        ld = DataLoader(ArrayDS(), batch_size=4, prefetch_to_device=2)
        with warnings.catch_warnings():
            warnings.simplefilter("ignore")
            got = [_np(b[0]) for b in ld]
    finally:
        chaos.reset()
    assert len(got) == 5 and all(np.array_equal(a, b)
                                 for a, b in zip(ref, got))
    assert ld._last_prefetcher.stats["refetch"] == 1
    assert metrics.counter("io.prefetch.refetch").value == r0 + 1


def test_prefetcher_retries_exhausted_raises():
    chaos.configure("loader.worker:fail@1-", seed=0)   # every call fails
    try:
        ld = DataLoader(ArrayDS(), batch_size=4, prefetch_to_device=2)
        with pytest.raises(RuntimeError, match="refetches"), \
                warnings.catch_warnings():
            warnings.simplefilter("ignore")
            list(ld)
    finally:
        chaos.reset()


def test_prefetcher_sharding_aware():
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed.parallel import input_sharding_fn
    devs = jax.devices()
    assert len(devs) >= 8, "conftest forces an 8-device host platform"
    mesh = Mesh(np.asarray(devs[:4]), ("dp",))
    fn = input_sharding_fn(mesh, "dp")
    # divisible dim0 -> split, scalar/indivisible -> replicated
    assert fn(np.zeros((8, 3))) == NamedSharding(mesh, P("dp"))
    assert fn(np.zeros((7, 3))) == NamedSharding(mesh, P())
    assert fn(np.float32(1.0)) == NamedSharding(mesh, P())
    batches = [(np.ones((8, 4), np.float32),
                np.zeros((8, 1), np.int32)) for _ in range(3)]
    for bx, _by in DevicePrefetcher(iter(batches), depth=2, sharding=fn):
        assert bx.sharding == NamedSharding(mesh, P("dp"))
    assert input_sharding_fn(mesh, "missing_axis") is None


# ---------------------------------------------------------------------------
# slot-buffered collate (single-copy fix)
# ---------------------------------------------------------------------------

def test_slot_collate_matches_default():
    c = _SlotCollate()
    rng = np.random.RandomState(3)
    samples = [(rng.rand(3, 2).astype("float32"), float(i), i,
                {"k": rng.rand(2).astype("float64")}, "s%d" % i)
               for i in range(4)]
    got = c(list(samples))
    ref = default_collate_fn(list(samples))
    for g, r in zip(got, ref):
        if isinstance(g, dict):
            assert np.array_equal(_np(g["k"]), _np(r["k"]))
            assert g["k"]._data.dtype == r["k"]._data.dtype  # f64 -> f32
        elif isinstance(g, list):
            assert g == r                       # strings stay a list
        else:
            assert np.array_equal(_np(g), _np(r))
            assert g._data.dtype == r._data.dtype


def test_slot_collate_buffer_reuse_never_corrupts():
    c = _SlotCollate()
    first = c([np.full((2, 2), 1.0, np.float32),
               np.full((2, 2), 2.0, np.float32)])
    kept = _np(first).copy()
    # same shapes/dtype -> same staging buffer gets overwritten
    c([np.full((2, 2), 9.0, np.float32)] * 2)
    assert np.array_equal(_np(first), kept)


def test_slot_collate_mixed_dtype_falls_back_to_promotion():
    c = _SlotCollate()
    batch = [np.zeros(2, np.int32), np.ones(2, np.int64)]
    got = c(list(batch))
    ref = default_collate_fn(list(batch))
    assert got._data.dtype == ref._data.dtype
    assert np.array_equal(_np(got), _np(ref))


def test_slot_collate_host_mode_stays_on_host():
    """Fork workers flip host_arrays: EVERY leaf type must come back as
    plain host data (np arrays / lists), never a device Tensor — a
    forked child entering jax is the classic inherited-lock deadlock."""
    c = _SlotCollate()
    c.host_arrays = True
    t = paddle.to_tensor(np.ones(2, np.float32))
    batch = [(np.full((2, 2), i, np.float32), float(i), i, t, "s",
              np.zeros(3, np.int32) if i == 0 else np.zeros(3, np.int64))
             for i in range(3)]
    arr, f, n, tt, s, mixed = c(list(batch))
    assert type(arr) is np.ndarray and arr.dtype == np.float32
    assert type(f) is np.ndarray and f.dtype == np.float32
    assert type(n) is np.ndarray          # ints: canonicalized by parent
    assert type(tt) is np.ndarray and np.array_equal(tt, np.ones((3, 2)))
    assert s == ["s"] * 3
    assert type(mixed) is np.ndarray      # promotion, still on host


def test_float_scalar_collate_single_conversion():
    out = default_collate_fn([0.5, 1.5, 2.5])
    assert str(out._data.dtype) == "float32"
    assert np.allclose(_np(out), [0.5, 1.5, 2.5])


# ---------------------------------------------------------------------------
# sync-free fit loop + step phases
# ---------------------------------------------------------------------------

def _fit_once(prefetch, steps=10, log_freq=5, verbose=2, trace=False):
    paddle.seed(99)
    net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.Tanh(),
                               paddle.nn.Linear(8, 2))
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.SGD(learning_rate=0.05,
                                       parameters=net.parameters()),
                  paddle.nn.CrossEntropyLoss())

    class DS(Dataset):
        def __getitem__(self, i):
            rng = np.random.RandomState(i)
            return rng.rand(4).astype("float32"), rng.randint(0, 2, (1,))

        def __len__(self):
            return steps * 4

    caught = []

    class Cap(Callback):
        def on_train_batch_end(self, step, logs=None):
            caught.append(logs["loss"])

    fetch0 = metrics.counter("train.loss_fetch").value
    if trace:
        tracer.enable()
    try:
        model.fit(DS(), batch_size=4, epochs=1, shuffle=False,
                  verbose=verbose, log_freq=log_freq, callbacks=[Cap()],
                  prefetch_to_device=prefetch)
    finally:
        if trace:
            tracer.disable()
    fetches_in_fit = metrics.counter("train.loss_fetch").value - fetch0
    return model, [float(l) for l in caught], fetches_in_fit


def test_fit_prefetch_default_and_bit_exact():
    _, ref, _ = _fit_once(0, verbose=0)
    model, got, _ = _fit_once(None, verbose=0)  # None -> flag default (2)
    assert model._last_prefetcher is not None, \
        "Model.fit should device-prefetch by default"
    assert ref == got


def test_fit_loss_fetch_bounded_per_log_window():
    """The satellite contract: the steady-state train loop performs at
    most one host block (lazy-loss materialization) per log_freq
    window.  20 steps @ log_freq=5, verbose=2 -> 4 window prints + the
    epoch-end line."""
    _, _, in_fit = _fit_once(None, steps=20, log_freq=5, verbose=2)
    assert 0 < in_fit <= 20 // 5 + 2, in_fit


def test_fit_verbose0_never_touches_the_loss():
    c = metrics.counter("train.loss_fetch")
    v0 = c.value
    paddle.seed(5)
    net = paddle.nn.Linear(4, 2)
    model = paddle.Model(net)
    model.prepare(paddle.optimizer.SGD(learning_rate=0.1,
                                       parameters=net.parameters()),
                  paddle.nn.MSELoss())
    x = np.random.RandomState(0).rand(16, 4).astype("float32")
    ds = paddle.io.TensorDataset([x, x[:, :2] * 0.5])
    model.fit(ds, batch_size=4, epochs=1, shuffle=False, verbose=0)
    assert c.value == v0, "verbose=0 fit must not materialize the loss"


def test_step_phase_breakdown_recorded():
    for name in ("train.step.data_wait_ms", "train.step.host_ms",
                 "train.step.device_ms"):
        h = metrics.histogram(name)
        h.reset()
    _fit_once(2, steps=6, verbose=0, trace=True)
    for name in ("train.step.data_wait_ms", "train.step.host_ms",
                 "train.step.device_ms"):
        snap = metrics.histogram(name).snapshot()
        assert snap.get("count", 0) >= 6, (name, snap)
    # attribution sanity: phases are non-negative and host excludes the
    # dispatch span it subtracts
    assert metrics.histogram("train.step.host_ms").snapshot()["min"] >= 0


def test_phase_hooks_cost_one_predicate_when_off():
    h = metrics.histogram("train.step.data_wait_ms")
    h.reset()
    _fit_once(2, steps=4, verbose=0, trace=False)
    assert h.snapshot().get("count", 0) == 0


def test_lazy_scalar_counts_materializations():
    from paddle_tpu.hapi.model import _LazyScalar
    import jax.numpy as jnp
    c = metrics.counter("train.loss_fetch")
    v0 = c.value
    s = _LazyScalar(jnp.float32(1.5), origin="test")
    assert float(s) == 1.5 and float(s) == 1.5
    assert c.value == v0 + 1      # second coercion hits the cached value


# ---------------------------------------------------------------------------
# deferred VisualDL flush
# ---------------------------------------------------------------------------

def test_visualdl_defers_coercion_to_flush(tmp_path):
    import json
    from paddle_tpu.hapi.callbacks import VisualDL

    class CountingLoss:
        def __init__(self, v):
            self.v = v
            self.coerced = 0

        def __float__(self):
            self.coerced += 1
            return self.v

    import numbers
    numbers.Number.register(CountingLoss)   # passes isinstance(Number)

    cb = VisualDL(log_dir=str(tmp_path))
    cb.on_train_begin()
    vals = [CountingLoss(float(i)) for i in range(5)]
    for i, v in enumerate(vals):
        cb.on_train_batch_end(i, {"loss": v, "batch_size": 4})
        assert v.coerced == 0, "per-step logging must stay lazy"
    cb.on_epoch_end(0)
    assert all(v.coerced == 1 for v in vals)
    cb.on_train_end()
    lines = [json.loads(l) for l in
             open(os.path.join(str(tmp_path), "scalars.jsonl"))]
    assert [l["loss"] for l in lines] == [0.0, 1.0, 2.0, 3.0, 4.0]


# ---------------------------------------------------------------------------
# checkpoint want_save gating
# ---------------------------------------------------------------------------

def test_checkpointer_want_save_interval(tmp_path):
    from paddle_tpu.distributed.checkpoint import AsyncCheckpointer
    ck = AsyncCheckpointer(str(tmp_path / "ck"), save_interval_steps=3)
    assert ck.want_save(0)
    import jax.numpy as jnp
    ck.save(0, {"w": jnp.zeros((2,))})
    assert not ck.want_save(1) and not ck.want_save(2)
    assert ck.want_save(3)
    ck.wait_until_finished()


# ---------------------------------------------------------------------------
# persistent compilation cache accounting (placement: test_placement.py)
# ---------------------------------------------------------------------------

def test_compile_cache_entry_count(tmp_path):
    d = str(tmp_path / "xla_cache")
    assert compile_cache.entry_count(d) == 0      # not created yet
    os.makedirs(d)
    open(os.path.join(d, "jit_f-0123-cache"), "w").close()
    open(os.path.join(d, "jit_f-0123-atime"), "w").close()
    assert compile_cache.entry_count(d) == 1
