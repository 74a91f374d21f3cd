"""ASP sparsity, strategy meta-optimizers, and parameter-server shim tests."""
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

import paddle_tpu as paddle
import paddle_tpu.nn as nn
from paddle_tpu import sparsity


# -- sparsity utils ----------------------------------------------------------
def test_mask_1d_roundtrip():
    rng = np.random.RandomState(0)
    w = rng.randn(8, 16).astype(np.float32)
    mask = sparsity.get_mask_1d(w, 2, 4)
    assert sparsity.check_mask_1d(w * mask, 2, 4)
    assert not sparsity.check_mask_1d(w, 2, 4)
    np.testing.assert_allclose(sparsity.calculate_density(w * mask), 0.5)
    # magnitudes: within each 4-chunk the 2 largest survive
    chunk = np.abs(w[0, :4])
    kept = mask[0, :4].astype(bool)
    assert set(np.argsort(chunk)[-2:]) == set(np.nonzero(kept)[0])


def test_mask_2d_variants():
    rng = np.random.RandomState(1)
    w = rng.randn(8, 8).astype(np.float32)
    for fn in (sparsity.get_mask_2d_greedy, sparsity.get_mask_2d_best):
        mask = fn(w, 2, 4)
        assert sparsity.check_mask_2d(w * mask, 2, 4), fn.__name__
        np.testing.assert_allclose(mask.sum(), w.size * 0.5)
    # best >= greedy in retained magnitude
    g = np.abs(w * sparsity.get_mask_2d_greedy(w, 2, 4)).sum()
    b = np.abs(w * sparsity.get_mask_2d_best(w, 2, 4)).sum()
    assert b >= g - 1e-5


def test_prune_model_and_decorated_optimizer():
    paddle.seed(0)
    net = nn.Linear(64, 64)
    masks = sparsity.prune_model(net, n=2, m=4)
    assert sparsity.check_sparsity(net.weight, n=2, m=4)
    opt = sparsity.decorate(
        paddle.optimizer.SGD(learning_rate=0.1,
                             parameters=net.parameters()), masks)
    x = paddle.to_tensor(np.random.RandomState(0).rand(4, 64)
                         .astype(np.float32))
    loss = paddle.mean(net(x) ** 2)
    loss.backward()
    opt.step()
    # pattern preserved after a dense-gradient update
    assert sparsity.check_sparsity(net.weight, n=2, m=4)
    assert sparsity.calculate_density(net.weight) <= 0.5 + 1e-6


def test_excluded_layers():
    sparsity.reset_excluded_layers()
    sparsity.set_excluded_layers(["skip_me"])
    paddle.seed(0)
    net = nn.Linear(64, 64)
    assert not sparsity.ASPHelper.supported("skip_me", net.weight)
    assert sparsity.ASPHelper.supported("keep", net.weight)
    sparsity.reset_excluded_layers()


# -- strategy meta-optimizers ------------------------------------------------
def _quad_setup():
    paddle.seed(0)
    from paddle_tpu.core.tensor import Parameter
    p = Parameter(np.array([4.0, -2.0], np.float32))
    return p


def test_gradient_merge_optimizer():
    from paddle_tpu.distributed.fleet.meta_optimizers import (
        GradientMergeOptimizer)
    p = _quad_setup()
    inner = paddle.optimizer.SGD(learning_rate=0.5, parameters=[p])
    opt = GradientMergeOptimizer(inner, k_steps=2, avg=True)
    w0 = p.numpy().copy()
    p._accumulate_grad(np.array([1.0, 1.0], np.float32))
    opt.step()                       # swallowed
    np.testing.assert_allclose(p.numpy(), w0)
    p._accumulate_grad(np.array([3.0, 3.0], np.float32))
    opt.step()                       # applies mean grad = 2
    np.testing.assert_allclose(p.numpy(), w0 - 0.5 * 2.0)


def test_localsgd_and_fp16_allreduce_single_rank():
    from paddle_tpu.distributed.fleet.meta_optimizers import (
        LocalSGDOptimizer, FP16AllReduceOptimizer)
    p = _quad_setup()
    inner = paddle.optimizer.SGD(learning_rate=0.5, parameters=[p])
    opt = LocalSGDOptimizer(inner, k_steps=2)
    p._accumulate_grad(np.array([2.0, 2.0], np.float32))
    w0 = p.numpy().copy()
    opt.step()
    np.testing.assert_allclose(p.numpy(), w0 - 1.0)   # world=1: avg==self

    p2 = _quad_setup()
    inner2 = paddle.optimizer.SGD(learning_rate=1.0, parameters=[p2])
    opt2 = FP16AllReduceOptimizer(inner2, wire_dtype="bfloat16")
    p2._accumulate_grad(np.array([1.0, -1.0], np.float32))
    w0 = p2.numpy().copy()
    opt2.step()
    np.testing.assert_allclose(p2.numpy(), w0 - [1.0, -1.0], rtol=1e-2)


def test_dgc_momentum_error_feedback():
    from paddle_tpu.distributed.fleet.meta_optimizers import (
        DGCMomentumOptimizer)
    p = _quad_setup()
    inner = paddle.optimizer.SGD(learning_rate=1.0, parameters=[p])
    opt = DGCMomentumOptimizer(inner, momentum=0.0, sparsity=0.5)
    # grad [3, 1]: top-50% keeps the 3, residual holds the 1
    p._accumulate_grad(np.array([3.0, 1.0], np.float32))
    w0 = p.numpy().copy()
    opt.step()
    np.testing.assert_allclose(p.numpy(), w0 - [3.0, 0.0])
    import jax.numpy as jnp
    resid = list(opt._v.values())[0]
    np.testing.assert_allclose(np.asarray(resid), [0.0, 1.0])
    # next step: zero grad, residual 1 accumulates and ships
    p.clear_gradient()
    p._accumulate_grad(np.array([0.0, 0.0], np.float32))
    w1 = p.numpy().copy()
    opt.step()
    np.testing.assert_allclose(p.numpy(), w1 - [0.0, 1.0])


# -- parameter server --------------------------------------------------------
def _free_port():
    from conftest import free_port
    return free_port()


def test_ps_dense_sparse_roundtrip(tmp_path):
    from paddle_tpu.distributed.fleet.ps import (PSServer, PSClient,
                                                 AdagradSGDRule)
    eps = [f"127.0.0.1:{_free_port()}" for _ in range(2)]
    servers = [PSServer(ep) for ep in eps]
    for s in servers:
        s.add_sparse_table("emb", dim=4)
    # dense table lives on its hash-designated shard; add to both (only
    # the designated one is ever addressed)
    for s in servers:
        s.add_dense_table("w", (3,))
        s.start()
    try:
        client = PSClient(eps)
        client.set_dense("w", np.array([1.0, 2.0, 3.0], np.float32))
        client.push_dense("w", np.array([10.0, 10.0, 10.0], np.float32))
        got = client.pull_dense("w")
        np.testing.assert_allclose(got, [0.5, 1.5, 2.5])  # lr=0.05

        keys = np.array([1, 2, 3, 1002, 1003], np.int64)
        rows = client.pull_sparse("emb", keys)
        assert rows.shape == (5, 4)
        # deterministic lazy init: same key -> same row
        rows2 = client.pull_sparse("emb", keys[:2])
        np.testing.assert_allclose(rows2, rows[:2])
        # push grads (duplicate key accumulates)
        client.push_sparse("emb", np.array([1, 1], np.int64),
                           np.ones((2, 4), np.float32))
        after = client.pull_sparse("emb", np.array([1], np.int64))
        np.testing.assert_allclose(after, rows[0:1] - 0.05 * 2.0, rtol=1e-5)

        # async push future
        f = client.push_sparse_async("emb", np.array([2], np.int64),
                                     np.ones((1, 4), np.float32))
        f.result(timeout=30)

        # save / load roundtrip
        client.save(str(tmp_path / "ckpt"))
        client.push_dense("w", np.array([100.0, 100.0, 100.0], np.float32))
        client.load(str(tmp_path / "ckpt"))
        np.testing.assert_allclose(client.pull_dense("w"), [0.5, 1.5, 2.5])
        client.close()
    finally:
        for s in servers:
            s.stop()


def test_ps_multiprocess_via_fleet(tmp_path):
    """Server in a separate process; worker uses fleet.init_worker —
    the reference TestDistBase PS pattern."""
    port = _free_port()
    server_script = tmp_path / "server.py"
    server_script.write_text(textwrap.dedent(f"""
        import os
        os.environ["PADDLE_TRAINING_ROLE"] = "PSERVER"
        os.environ["PADDLE_PSERVERS_IP_PORT_LIST"] = "127.0.0.1:{port}"
        from paddle_tpu.distributed.fleet import init_server
        srv = init_server()
        srv.add_sparse_table("emb", dim=3)
        srv.run()
        """))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.dirname(os.path.dirname(
                   os.path.abspath(__file__))))
    proc = subprocess.Popen([sys.executable, str(server_script)], env=env)
    try:
        os.environ["PADDLE_PSERVERS_IP_PORT_LIST"] = f"127.0.0.1:{port}"
        from paddle_tpu.distributed import fleet as fleet_mod
        deadline = time.time() + 60
        client = None
        while time.time() < deadline:
            try:
                client = fleet_mod.init_worker()
                client._call(client._endpoints[0], ("ping",))
                break
            except (ConnectionError, OSError):
                time.sleep(0.5)
        assert client is not None, "server never came up"
        rows = client.pull_sparse("emb", np.array([7, 8], np.int64))
        assert rows.shape == (2, 3)
        client.push_sparse("emb", np.array([7], np.int64),
                           np.ones((1, 3), np.float32))
        after = client.pull_sparse("emb", np.array([7], np.int64))
        np.testing.assert_allclose(after[0], rows[0] - 0.05, rtol=1e-5)
        fleet_mod.stop_worker()
    finally:
        proc.terminate()
        proc.wait(timeout=10)
        os.environ.pop("PADDLE_PSERVERS_IP_PORT_LIST", None)
