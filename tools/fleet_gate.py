#!/usr/bin/env python
"""CI fleet gate (ISSUE 13): a 2-replica serving fleet behind the
failover router must survive a dispatch-hop kill, an in-flight-bound
shed, a mid-traffic weight hot-swap, and a replica SIGKILL — with
zero lost requests, bit-exact streams, and exact counts.

Legs (one fleet, run in sequence):

0. form    — 2 replica subprocesses (GenerationEngine over a tiny GPT,
             warmup, watch_dir primed with a step-1 checkpoint of
             seed-0 weights) register TTL leases; the in-process
             router discovers both (flight ``replica.join`` == 2).
1. chaos   — ``router.dispatch:fail@3`` kills exactly one forward hop
             mid-burst: 5/5 requests complete bit-exact vs a local
             session reference, ``chaos.injected.router.dispatch`` ==
             1 and ``fleet.router.retry`` == 1, EXACTLY.
2. shed    — router pinned to max_inflight=0: 3 requests -> three
             typed 429s with ``Retry-After``; ``fleet.router.shed``
             == 3, EXACTLY; nothing reached a replica.
3. hot-swap— 4 long SSE streams run while a step-2 checkpoint
             (different weights, sha256-verified commit) lands in the
             watched directory: the router canaries ONE replica,
             passes the error-rate window on live traffic, promotes
             the other — zero dropped streams (40/40 tokens each),
             post-promote responses bit-exact vs the NEW weights,
             flight ``swap.canary`` == 1, ``swap.promote`` == 1,
             ``swap.rollback`` == 0.
4. SIGKILL — 8 concurrent requests (mixed stream/JSON) while one
             replica dies by SIGKILL: the router re-spreads (SSE
             splice for mid-stream victims) and all 8 complete
             bit-exact vs the new-weight references — zero lost;
             membership drops to 1 (flight ``replica.leave`` == 1).
5. drain   — SIGTERM to the survivor: graceful drain path exits 0.

Wired into tools/run_all_tests.sh next to the serving/decode gates.
"""
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import numpy as np  # noqa: E402

JOB = "fleetgate"
MAX_NEW = 8
LONG_NEW = 40
PROMPT = list(range(1, 9))

WORKER = """
import os, sys
os.environ.setdefault("JAX_PLATFORMS", "cpu")
sys.path.insert(0, {repo!r})
import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.serving import fleet
from paddle_tpu.models import GPT, GPTConfig

spec, rid, wdir = sys.argv[1], sys.argv[2], sys.argv[3]
paddle.seed(777)   # scrambled boot weights; the watch dir is truth
net = GPT(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=64, ffn_mult=2))
eng = serving.GenerationEngine(net, serving.GenerationEngineConfig(
    max_slots=4, max_length=64, max_new_tokens={max_new}, warmup=True,
    # the SIGKILL leg funnels the WHOLE fleet's long-stream burst onto
    # one survivor: give its token-budget admission room for all of it
    # (the shed path has its own dedicated leg at the router tier)
    max_tokens_in_flight=4096))
rep = fleet.FleetReplica(
    generation_engine=eng, store=spec, job={job!r}, replica_id=rid,
    watch_dir=wdir, watch_interval=0.2, heartbeat_interval=0.2,
    lease_ttl=2.0)
rep.run()
"""


def val(name):
    from paddle_tpu.profiler import metrics
    m = metrics.get(name)
    return m.value if m is not None else 0


def net_for(seed):
    import paddle_tpu as paddle
    from paddle_tpu.models import GPT, GPTConfig
    paddle.seed(seed)
    return GPT(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                         num_heads=2, max_seq_len=64, ffn_mult=2))


def post(url, payload, timeout=180):
    req = urllib.request.Request(
        url + "/v1/generate", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    return urllib.request.urlopen(req, timeout=timeout)


def gen_json(url, seed, max_new=MAX_NEW, prompt=PROMPT):
    body = json.load(post(url, {"prompt_ids": prompt,
                                "max_new_tokens": max_new,
                                "do_sample": True, "temperature": 0.8,
                                "top_k": 12, "seed": seed}))
    return body["tokens"]


def gen_stream(url, seed, max_new=MAX_NEW, prompt=PROMPT):
    resp = post(url, {"prompt_ids": prompt, "max_new_tokens": max_new,
                      "do_sample": True, "temperature": 0.8,
                      "top_k": 12, "seed": seed, "stream": True})
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


def main():
    import paddle_tpu as paddle
    from paddle_tpu.distributed import checkpoint as ckpt
    from paddle_tpu.distributed.fleet.elastic.manager import KVServer
    from paddle_tpu.generation import GenerationSession
    from paddle_tpu.profiler import flight
    from paddle_tpu.serving import fleet

    work = tempfile.mkdtemp(prefix="fleet_gate_")
    wdir = os.path.join(work, "ckpts")
    os.makedirs(wdir)

    # step 1: the fleet's boot weights (seed 0)
    p1, b1 = net_for(0).functional_state()
    ckpt.save_state(os.path.join(wdir, "1"),
                    {"params": p1, "buffers": b1}, step=1)

    kv = KVServer().start()
    spec = f"tcp://{kv.endpoint}"

    script = os.path.join(work, "replica.py")
    with open(script, "w") as f:
        f.write(WORKER.format(repo=REPO, job=JOB, max_new=MAX_NEW))
    env = dict(os.environ)
    # the replicas share compiled programs through JAX's persistent
    # cache at its fixed path (utils/compile_cache.py)
    procs = [subprocess.Popen([sys.executable, script, spec,
                               f"g{i}", wdir], env=env)
             for i in (1, 2)]

    flight.clear()
    router = fleet.FleetRouter(
        spec, JOB, refresh_interval=0.1, probe_interval=0.25,
        canary_requests=2, canary_max_errors=0).start()
    url = f"http://{router.host}:{router.port}"

    try:
        # ---- leg 0: fleet formation -------------------------------------
        deadline = time.time() + 240
        while time.time() < deadline:
            if len(router._dispatchable()) == 2:
                break
            dead = [p.poll() for p in procs if p.poll() is not None]
            assert not dead, f"replica died during startup: {dead}"
            time.sleep(0.2)
        assert len(router._dispatchable()) == 2, \
            f"fleet never formed: {router.health()}"
        c = flight.counts()
        assert c.get("replica.join") == 2, c
        print(f"fleet gate: formed 2 replicas behind {url}")

        # local bit-exact references, matching the replica session
        # geometry exactly (bit-parity needs identical executables)
        ses_old = GenerationSession(net_for(0), batch_capacity=4,
                                    max_length=64, name="refold")
        prompt_arr = np.asarray(PROMPT, np.int32)

        def ref(session, seed, max_new=MAX_NEW):
            return session.generate(
                [prompt_arr], max_new_tokens=max_new, do_sample=True,
                temperature=0.8, top_k=12, seed=seed)[0].tolist()

        # ---- leg 1: dispatch-hop chaos, exact counts --------------------
        paddle.set_flags(
            {"FLAGS_chaos_spec": "router.dispatch:fail@3"})
        try:
            got = [gen_json(url, seed=100 + i) for i in range(5)]
        finally:
            paddle.set_flags({"FLAGS_chaos_spec": ""})
        for i, toks in enumerate(got):
            expect = ref(ses_old, 100 + i)
            assert toks == expect, \
                (f"chaos leg request {i}: {toks} != {expect}")
        inj = val("chaos.injected.router.dispatch")
        retries = val("fleet.router.retry")
        assert inj == 1, f"expected exactly 1 injected hop kill: {inj}"
        assert retries == 1, f"expected exactly 1 failover retry: " \
            f"{retries}"
        print("fleet gate: chaos leg OK — 5/5 bit-exact, 1 injected "
              "hop kill, 1 failover retry")

        # ---- leg 2: typed shed at the in-flight bound -------------------
        old_inflight = router.max_inflight
        router.max_inflight = 0
        shed_before = val("fleet.router.shed")
        for _ in range(3):
            try:
                post(url, {"prompt_ids": PROMPT})
                raise AssertionError("overloaded router answered 200")
            except urllib.error.HTTPError as e:
                assert e.code == 429, e.code
                assert e.headers.get("Retry-After"), "no Retry-After"
                assert json.loads(e.read().decode())["reason"] == \
                    "router_overload"
        router.max_inflight = old_inflight
        assert val("fleet.router.shed") == shed_before + 3
        print("fleet gate: shed leg OK — 3 typed 429s with "
              "Retry-After, exact count")

        # ---- leg 3: mid-traffic hot-swap (canary -> promote) ------------
        streams = {}

        def long_stream(seed):
            streams[seed] = gen_stream(url, seed, max_new=LONG_NEW)

        threads = [threading.Thread(target=long_stream, args=(s,))
                   for s in (201, 202, 203, 204)]
        for t in threads:
            t.start()
        time.sleep(0.3)        # streams are live mid-generation
        p2, b2 = net_for(1).functional_state()
        ckpt.save_state(os.path.join(wdir, "2"),
                        {"params": p2, "buffers": b2}, step=2)
        deadline = time.time() + 90
        seed = 300
        while time.time() < deadline:
            if flight.counts().get("swap.promote"):
                break
            gen_json(url, seed=seed)   # traffic feeds the canary window
            seed += 1
            time.sleep(0.05)
        for t in threads:
            t.join(timeout=180)
        c = flight.counts()
        assert c.get("swap.canary") == 1, c
        assert c.get("swap.promote") == 1, c
        assert not c.get("swap.rollback"), c
        # zero dropped streams across the swap: every long stream got
        # its full budget (a stream spanning the swap mixes old/new
        # tokens — completeness, not token values, is its contract)
        assert sorted(streams) == [201, 202, 203, 204]
        for s, toks in streams.items():
            assert len(toks) == LONG_NEW, \
                f"stream {s} dropped tokens: {len(toks)}/{LONG_NEW}"
        # served bytes flipped: post-promote traffic is the NEW weights
        ses_new = GenerationSession(net_for(1), batch_capacity=4,
                                    max_length=64, name="refnew")
        post_swap = gen_json(url, seed=999)
        expect = ref(ses_new, 999)
        assert post_swap == expect, (post_swap, expect)
        old_expect = ref(ses_old, 999)
        assert post_swap != old_expect, \
            "post-swap tokens still match the OLD weights"
        assert router.health()["current_step"] == 2
        print("fleet gate: hot-swap leg OK — canary promoted, 4/4 "
              f"streams x {LONG_NEW} tokens across the swap, served "
              "bytes flipped to step 2")

        # ---- leg 4: SIGKILL one replica mid-traffic ---------------------
        results, errors = {}, []

        def client(i):
            try:
                seed = 400 + i
                if i % 2:
                    results[i] = gen_stream(url, seed,
                                            max_new=LONG_NEW)
                else:
                    results[i] = gen_json(url, seed,
                                          max_new=LONG_NEW)
            except Exception as e:   # noqa: BLE001 — a loss is a failure
                errors.append((i, repr(e)))

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(8)]
        for t in threads:
            t.start()
        time.sleep(0.3)            # requests are in flight
        procs[0].send_signal(signal.SIGKILL)
        for t in threads:
            t.join(timeout=240)
        assert not errors, f"lost requests after SIGKILL: {errors}"
        assert sorted(results) == list(range(8))
        for i, toks in results.items():
            expect = ref(ses_new, 400 + i, max_new=LONG_NEW)
            assert toks == expect, \
                (f"request {i} not bit-exact after failover: "
                 f"{toks} != {expect}")
        # membership converges to the survivor; the victim leaves ONCE
        deadline = time.time() + 15
        while time.time() < deadline:
            if len(router._replicas) == 1:
                break
            time.sleep(0.1)
        assert set(router._replicas) == {"g2"}, router.health()
        c = flight.counts()
        assert c.get("replica.leave") == 1, c
        assert c.get("replica.join") == 2, c
        print("fleet gate: SIGKILL leg OK — 8/8 requests bit-exact "
              "through failover, membership 2 -> 1, exact "
              "join/leave counts")

        # ---- leg 5: graceful drain of the survivor ----------------------
        procs[1].send_signal(signal.SIGTERM)
        rc = procs[1].wait(timeout=60)
        assert rc == 0, f"survivor drain exited {rc}"
        print("fleet gate: drain leg OK — survivor exited 0 after "
              "graceful drain")

        print("fleet gate OK: chaos failover, typed shed, "
              "canary-promoted hot-swap, SIGKILL re-spread, drain — "
              "all exact")
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
