#!/usr/bin/env python
"""CI disagg gate (ISSUE 19): a 1-prefill + 2-decode fleet behind the
prefix-aware router must serve a shared-prompt workload bit-exact vs a
monolithic reference, keep the fleet-wide prefix hit rate at the
single-replica level, and ride out an injected KV-transfer failure —
with zero lost requests and every pool drained to all-free.

Legs (one fleet, run in sequence):

0. form      — 3 replica subprocesses (paged engines over one seed-0
               GPT): ``pre`` (role=prefill), ``d1``/``d2``
               (role=decode, d2 armed with ``kv.transfer:fail@1``).
               The router discovers all 3 but dispatches to exactly
               the 2 decode replicas (prefill is filtered).
1. chaos     — the first cold request lands DIRECTLY on d2: its one
               chain pull from ``pre`` dies by injection
               (``chaos.injected.kv.transfer`` == 1,
               ``kv.transfer.fail`` >= 1 on d2) and the request
               completes bit-exact anyway via local re-prefill —
               a transfer failure costs latency, never a token.
2. traffic   — 2 shared 24-token heads x 3 suffix variants x
               (greedy + 2 sampled configs), JSON and SSE, through
               the router: every stream bit-exact vs a monolithic
               PagedGenerationEngine reference with identical
               geometry; chains actually flow (``kv.transfer.fetch``
               >= 1 on d1) and at least one dispatch is steered by a
               published prefix head (``fleet.router.prefix_routed``).
3. hit rate  — fleet-wide prefix-cache hit rate (both decode
               replicas, probe lookups included) within 0.15 of the
               monolith's rate on the same workload (the ROADMAP
               "fleet hit rate ~= single-replica rate" gate).
4. drain     — SIGTERM all replicas: graceful drain, every worker
               asserts its pool returned to all-free (exit 3 on a
               leaked block) and exits 0.

Wired into tools/run_all_tests.sh next to the fleet and slo gates.
"""
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.error
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

JOB = "disagggate"
MAX_NEW = 8
BS = 8
HEAD_A = list(range(1, 25))          # 24 tokens = 3 full blocks
HEAD_B = list(range(30, 54))
SUFFIXES = [[60, 61, 62, 63], [70, 71, 72, 73], [80, 81, 82, 83]]
CONFIGS = [dict(do_sample=False, seed=7),
           dict(do_sample=True, temperature=0.9, top_k=0, top_p=1.0,
                seed=11),
           dict(do_sample=True, temperature=0.8, top_k=12, top_p=0.95,
                seed=13)]

WORKER = """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})
import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.serving import fleet
from paddle_tpu.models import GPT, GPTConfig

spec, rid, role = sys.argv[1], sys.argv[2], sys.argv[3]
paddle.seed(0)          # every replica serves identical weights
net = GPT(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=64, ffn_mult=2))
eng = serving.PagedGenerationEngine(net, serving.GenerationEngineConfig(
    max_slots=2, max_length=64, max_new_tokens={max_new},
    block_size={bs}, num_blocks=32, prefix_cache_blocks=16,
    warmup="off", name=rid))
rep = fleet.FleetReplica(
    generation_engine=eng, store=spec, job={job!r}, replica_id=rid,
    role=role, heartbeat_interval=0.2, lease_ttl=2.0)
rep.run()
if eng.pool.available != eng.pool.num_blocks:
    print("POOL LEAK:", eng.pool.available, "/", eng.pool.num_blocks,
          file=sys.stderr)
    sys.exit(3)
"""


def val(name):
    from paddle_tpu.profiler import metrics
    m = metrics.get(name)
    return m.value if m is not None else 0


def post(url, payload, timeout=300):
    req = urllib.request.Request(
        url + "/v1/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def gen_json(url, prompt, kw):
    return json.load(post(url, dict(prompt_ids=prompt,
                                    max_new_tokens=MAX_NEW,
                                    **kw)))["tokens"]


def gen_stream(url, prompt, kw):
    resp = post(url, dict(prompt_ids=prompt, max_new_tokens=MAX_NEW,
                          stream=True, **kw))
    toks, done = [], None
    for raw in resp:
        line = raw.decode().strip()
        if not line.startswith("data:"):
            continue
        d = json.loads(line[5:])
        if "token" in d:
            toks.append(d["token"])
        elif "done" in d:
            done = d
        elif "error" in d:
            raise RuntimeError(f"terminal stream error: {d}")
    assert done is not None, "stream ended without terminal event"
    assert done["tokens"] == toks, (done, toks)
    return toks


def scrape(url):
    """Prometheus text -> {name: float} (dots exported as _)."""
    with urllib.request.urlopen(url + "/metrics", timeout=30) as r:
        text = r.read().decode()
    out = {}
    for line in text.splitlines():
        if line.startswith("#") or "{" in line:
            continue
        parts = line.split()
        if len(parts) == 2:
            try:
                out[parts[0]] = float(parts[1])
            except ValueError:
                pass
    return out


def main():
    from paddle_tpu import serving
    from paddle_tpu.distributed.fleet.elastic.manager import KVServer
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.serving import fleet
    import paddle_tpu as paddle

    work = tempfile.mkdtemp(prefix="disagg_gate_")
    kv = KVServer().start()
    spec = f"tcp://{kv.endpoint}"

    script = os.path.join(work, "replica.py")
    with open(script, "w") as f:
        f.write(WORKER.format(repo=REPO, job=JOB, max_new=MAX_NEW,
                              bs=BS))
    env = dict(os.environ)
    # the replicas share compiled programs through JAX's persistent
    # cache at its fixed path (utils/compile_cache.py)
    env_chaos = dict(env, FLAGS_chaos_spec="kv.transfer:fail@1")
    procs = [
        subprocess.Popen([sys.executable, script, spec, "pre",
                          "prefill"], env=env),
        subprocess.Popen([sys.executable, script, spec, "d1",
                          "decode"], env=env),
        subprocess.Popen([sys.executable, script, spec, "d2",
                          "decode"], env=env_chaos),
    ]

    router = fleet.FleetRouter(spec, JOB, refresh_interval=0.1,
                               probe_interval=0.25,
                               manage_swaps=False).start()
    url = f"http://{router.host}:{router.port}"

    jobs = []           # (prompt, config, use_stream)
    for head in (HEAD_A, HEAD_B):
        for sfx in SUFFIXES:
            for j, kw in enumerate(CONFIGS):
                jobs.append((head + sfx, kw, j == 1))

    try:
        # ---- leg 0: formation — 3 known, 2 dispatchable -----------------
        deadline = time.time() + 240
        while time.time() < deadline:
            if len(router._replicas) == 3 \
                    and len(router._dispatchable()) == 2:
                break
            dead = [p.poll() for p in procs if p.poll() is not None]
            assert not dead, f"replica died during startup: {dead}"
            time.sleep(0.2)
        cands = {i.replica_id for i in router._dispatchable()}
        assert cands == {"d1", "d2"}, \
            f"prefill must be filtered from dispatch: {cands}"
        eps = {rid: i.endpoint
               for rid, i in router._replicas.items()}
        print(f"disagg gate: formed pre+d1+d2 behind {url} "
              "(prefill filtered from dispatch)")

        # monolithic reference: identical geometry on one engine
        paddle.seed(0)
        net = GPT(GPTConfig(vocab_size=97, hidden_size=32,
                            num_layers=2, num_heads=2, max_seq_len=64,
                            ffn_mult=2))
        mono = serving.PagedGenerationEngine(
            net, serving.GenerationEngineConfig(
                max_slots=2, max_length=64, max_new_tokens=MAX_NEW,
                block_size=BS, num_blocks=32, prefix_cache_blocks=16,
                warmup="off", name="dgmono"))
        refs = []
        for prompt, kw, _s in jobs:
            refs.append(mono.generate(
                np.asarray(prompt, np.int32), timeout=300,
                max_new_tokens=MAX_NEW, **kw).tolist())

        # ---- leg 1: injected transfer failure, ridden out ---------------
        # d2's FIRST chain pull dies by chaos: the request must still
        # complete bit-exact via local re-prefill (hit d2 directly so
        # the injection deterministically lands there)
        i_b0 = next(i for i, (p, kw, _s) in enumerate(jobs)
                    if p[:24] == HEAD_B and kw is CONFIGS[0])
        d2url = f"http://{eps['d2']}"
        got = gen_json(d2url, jobs[i_b0][0], jobs[i_b0][1])
        assert got == refs[i_b0], \
            f"chaos-leg stream not bit-exact: {got} != {refs[i_b0]}"
        m2 = scrape(d2url)
        assert m2.get("chaos_injected_kv_transfer") == 1, m2.get(
            "chaos_injected_kv_transfer")
        assert m2.get("kv_transfer_fail", 0) >= 1
        done = {i_b0}
        print("disagg gate: chaos leg OK — 1 injected kv.transfer "
              "kill, request bit-exact via local re-prefill, "
              "zero lost")

        # ---- leg 2: shared-prompt traffic through the router ------------
        first_a = next(i for i, (p, _kw, _s) in enumerate(jobs)
                       if p[:24] == HEAD_A)
        got = gen_json(url, jobs[first_a][0], jobs[first_a][1])
        assert got == refs[first_a]
        done.add(first_a)
        # wait for a decode replica to advertise the head it now holds
        deadline = time.time() + 30
        while time.time() < deadline:
            if any(i.prefix_heads for i in router._replicas.values()):
                break
            time.sleep(0.1)
        assert any(i.prefix_heads for i in router._replicas.values()), \
            "no replica ever published prefix_heads"
        for i, (prompt, kw, use_stream) in enumerate(jobs):
            if i in done:
                continue
            fn = gen_stream if use_stream else gen_json
            got = fn(url, prompt, kw)
            assert got == refs[i], \
                (f"request {i} not bit-exact: {got} != {refs[i]} "
                 f"({kw})")
        m1 = scrape(f"http://{eps['d1']}")
        assert m1.get("kv_transfer_fetch", 0) >= 1, \
            "d1 never adopted a chain from the prefill replica"
        assert val("fleet.router.prefix_routed") >= 1, \
            "no dispatch was ever steered by a published prefix head"
        print(f"disagg gate: traffic leg OK — {len(jobs)}/{len(jobs)} "
              "streams bit-exact vs the monolith (greedy + sampled), "
              f"{int(m1.get('kv_transfer_fetch', 0))} chains adopted "
              f"on d1, {int(val('fleet.router.prefix_routed'))} "
              "prefix-steered dispatches")

        # ---- leg 3: fleet hit rate ~= single-replica rate ---------------
        m2 = scrape(d2url)
        fleet_hit = m1.get("d1_prefix_cache_hit", 0) \
            + m2.get("d2_prefix_cache_hit", 0)
        fleet_miss = m1.get("d1_prefix_cache_miss", 0) \
            + m2.get("d2_prefix_cache_miss", 0)
        fleet_rate = fleet_hit / max(1.0, fleet_hit + fleet_miss)
        mono_hit = val("dgmono.prefix_cache.hit")
        mono_miss = val("dgmono.prefix_cache.miss")
        mono_rate = mono_hit / max(1.0, mono_hit + mono_miss)
        assert fleet_rate + 0.15 >= mono_rate, \
            (f"fleet prefix hit rate {fleet_rate:.3f} fell behind the "
             f"single-replica rate {mono_rate:.3f}")
        mono.close()
        assert mono.pool.available == mono.pool.num_blocks
        print(f"disagg gate: hit-rate leg OK — fleet {fleet_rate:.3f} "
              f"vs single-replica {mono_rate:.3f}")

        # ---- leg 4: graceful drain, pools all-free ----------------------
        for p in procs:
            p.send_signal(signal.SIGTERM)
        for i, p in enumerate(procs):
            rc = p.wait(timeout=60)
            assert rc == 0, \
                f"replica {i} drain exited {rc} (3 = leaked KV blocks)"
        print("disagg gate OK: prefill/decode split bit-exact vs the "
              "monolith, fleet hit rate held, injected transfer "
              "failure ridden out with zero lost requests, all pools "
              "drained to all-free")
    finally:
        router.stop()
        for p in procs:
            if p.poll() is None:
                p.kill()
                p.wait(timeout=10)
        kv.stop()
        shutil.rmtree(work, ignore_errors=True)


if __name__ == "__main__":
    main()
