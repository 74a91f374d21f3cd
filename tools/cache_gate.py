#!/usr/bin/env python
"""CI gate: the AOT artifact store drives second-process cold starts to
ZERO fresh XLA compiles.

Runs the same workload twice in two fresh processes sharing one
``FLAGS_compile_cache_dir``:

    1. a seeded ``Model.fit`` (the hapi jitted train step),
    2. a serving-engine load (``InferenceEngine`` with
       ``EngineConfig(warmup=True)`` over a saved artifact) + one
       request,
    3. a ``GenerationSession`` prefill/decode generate call.

Asserted contract:

- run 1 misses and stores artifacts (the store actually engaged);
- run 2 performs **zero** fresh XLA compiles: every AOT site hits the
  artifact store (``aot_store.miss == 0``, hits == run 1's misses) AND
  jax's persistent compilation cache gains **zero** new entries (so
  nothing compiled outside the store's sight either);
- run 2 is **bit-exact** with run 1: same final fit loss + parameter
  bytes, same served outputs, same generated tokens;
- run 2's cold start (fit wall time to first step) is no slower than
  2x run 1's — deserialization must actually be cheaper than
  compilation (generous bound: CI machines are noisy).

Usage: python tools/cache_gate.py          (parent: orchestrates)
       python tools/cache_gate.py --child  (one measured run)
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import tempfile
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def child(workdir: str):
    import numpy as np
    import paddle_tpu as paddle
    import paddle_tpu.nn as nn
    import paddle_tpu.io as io
    from paddle_tpu import serving
    from paddle_tpu.jit import InputSpec
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.generation import GenerationSession
    from paddle_tpu.utils import artifact_store as aot
    from paddle_tpu.utils import compile_cache as cc

    assert aot.active() is not None, \
        "artifact store not armed (FLAGS_compile_cache_dir unset?)"
    jax_entries0 = cc.entry_count()
    out = {}

    # -- leg 1: seeded fit ---------------------------------------------
    paddle.seed(0)
    net = nn.Sequential(nn.Linear(8, 16), nn.ReLU(), nn.Linear(16, 1))
    model = paddle.Model(net)
    opt = paddle.optimizer.SGD(learning_rate=0.1,
                               parameters=net.parameters())
    model.prepare(opt, paddle.nn.MSELoss())
    rng = np.random.RandomState(0)
    x = rng.rand(64, 8).astype("float32")
    y = rng.rand(64, 1).astype("float32")
    samples = [(x[i * 8:(i + 1) * 8], y[i * 8:(i + 1) * 8])
               for i in range(8)]

    class DS(io.Dataset):
        def __len__(self):
            return len(samples)

        def __getitem__(self, i):
            return samples[i]

    loader = io.DataLoader(DS(), batch_size=None, shuffle=False)
    t0 = time.perf_counter()
    model.fit(loader, epochs=1, verbose=0)
    out["fit_s"] = round(time.perf_counter() - t0, 3)
    out["fit_loss"] = repr(float(
        model.train_batch([x[:8]], [y[:8]])["loss"]))
    h = hashlib.sha256()
    for p in net.parameters():
        h.update(np.asarray(p._data).tobytes())
    out["fit_params_sha"] = h.hexdigest()

    # -- leg 2: serving-engine load + one request ----------------------
    paddle.seed(1)
    prefix = os.path.join(workdir, "model", "m")
    if not os.path.exists(prefix + ".pdmodel"):
        os.makedirs(os.path.dirname(prefix), exist_ok=True)
        snet = nn.Sequential(nn.Linear(8, 16), nn.ReLU(),
                             nn.Linear(16, 8))
        paddle.jit.save(snet, prefix, input_spec=[
            InputSpec([-1, 8], "float32", name="x")])
    engine = serving.InferenceEngine(prefix, serving.EngineConfig(
        max_batch_size=8, num_workers=1, warmup=True))
    out["warmed_buckets"] = engine.warmed_buckets
    served = engine.infer(
        [np.linspace(0, 1, 3 * 8).reshape(3, 8).astype("float32")],
        timeout=120)
    engine.close()
    out["serve_sha"] = hashlib.sha256(
        b"".join(np.ascontiguousarray(o).tobytes()
                 for o in served)).hexdigest()

    # -- leg 3: generation session -------------------------------------
    paddle.seed(2)
    cfg = GPTConfig(vocab_size=128, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=32, ffn_mult=2)
    gpt = GPT(cfg)
    sess = GenerationSession(gpt, batch_capacity=2, max_length=32)
    toks = sess.generate(
        [np.arange(1, 6, dtype=np.int32),
         np.arange(3, 12, dtype=np.int32)],
        max_new_tokens=8, do_sample=True, temperature=0.9,
        seeds=[11, 22])
    out["gen_tokens"] = [t.tolist() for t in toks]

    out["aot"] = aot.stats()
    out["jax_cache_new_entries"] = cc.entry_count() - jax_entries0
    print("CACHE_GATE_JSON " + json.dumps(out))


def run_child(workdir: str, cache_dir: str) -> dict:
    # the store is gated alone: with JAX's own persistent cache on, a
    # compile it serves is (rightly) not stored a second time
    env = dict(os.environ,
               JAX_PLATFORMS="cpu",
               JAX_ENABLE_COMPILATION_CACHE="false",
               FLAGS_compile_cache_dir=cache_dir,
               FLAGS_prefetch_to_device="2",
               PYTHONPATH=REPO + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    r = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "--child", workdir],
        env=env, capture_output=True, text=True, timeout=600, cwd=REPO)
    if r.returncode != 0:
        print(r.stdout)
        print(r.stderr, file=sys.stderr)
        raise SystemExit(f"cache_gate child failed (rc={r.returncode})")
    for line in r.stdout.splitlines():
        if line.startswith("CACHE_GATE_JSON "):
            return json.loads(line[len("CACHE_GATE_JSON "):])
    print(r.stdout)
    print(r.stderr, file=sys.stderr)
    raise SystemExit("cache_gate child emitted no JSON")


def main():
    if len(sys.argv) > 1 and sys.argv[1] == "--child":
        child(sys.argv[2])
        return
    base = tempfile.mkdtemp(prefix="paddle_cache_gate_")   # model files
    # the artifact store under test: a fixed path, emptied so that run 1
    # starts cold
    cache_dir = os.path.join(REPO, ".jax_cache", "cache_gate_store")
    shutil.rmtree(cache_dir, ignore_errors=True)
    r1 = run_child(base, cache_dir)
    r2 = run_child(base, cache_dir)
    print(f"[cache_gate] run1 aot={r1['aot']} "
          f"jax_new={r1['jax_cache_new_entries']} fit={r1['fit_s']}s")
    print(f"[cache_gate] run2 aot={r2['aot']} "
          f"jax_new={r2['jax_cache_new_entries']} fit={r2['fit_s']}s")

    # the store engaged on run 1
    assert r1["aot"]["miss"] > 0 and \
        r1["aot"]["store"] == r1["aot"]["miss"], \
        f"run 1 did not populate the artifact store: {r1['aot']}"
    assert r1["aot"]["hit"] == 0, \
        f"run 1 hit a supposedly-fresh store: {r1['aot']}"
    # run 2: zero fresh XLA compiles, everything from the store
    assert r2["aot"]["miss"] == 0 and r2["aot"]["corrupt"] == 0, \
        f"run 2 paid fresh AOT compiles: {r2['aot']}"
    assert r2["aot"]["hit"] == r1["aot"]["miss"], \
        (f"run 2 hits {r2['aot']['hit']} != run 1 misses "
         f"{r1['aot']['miss']} — an AOT site changed its fingerprint "
         "across identical processes")
    assert r2["jax_cache_new_entries"] == 0, \
        (f"run 2 compiled {r2['jax_cache_new_entries']} program(s) "
         "outside the artifact store (persistent-cache entries grew)")
    # bit-exactness across processes
    for k in ("fit_loss", "fit_params_sha", "serve_sha", "gen_tokens"):
        assert r1[k] == r2[k], \
            f"run 2 not bit-exact with run 1 on {k}: {r1[k]} vs {r2[k]}"
    assert r2["warmed_buckets"] == r1["warmed_buckets"] > 0
    # deserialization must actually beat compilation: generous 2x +
    # 1s slack absorbs 1-core CI noise while still catching a
    # pathologically slow store (run 1's fit includes every compile)
    assert r2["fit_s"] <= 2.0 * r1["fit_s"] + 1.0, \
        (f"warm fit ({r2['fit_s']}s) slower than 2x the cold fit "
         f"({r1['fit_s']}s) — artifact loads cost more than compiles?")
    print("[cache_gate] OK: second-process run performed 0 fresh XLA "
          f"compiles ({r2['aot']['hit']} artifact hits), bit-exact "
          "across fit + engine load + generation")


if __name__ == "__main__":
    main()
