#!/usr/bin/env python
"""CI observability gate: request tracing, fleet aggregation, flight
recorder — the three ISSUE-12 layers, end to end.

Phase 1 (traced burst, run TWICE in fresh processes for
bit-stability): a GenerationEngine behind the HTTP server answers a
request sent with a fixed W3C ``traceparent`` — the response must echo
the same trace_id, and the exported trace must contain the complete
ingress -> admission -> queue_wait -> prefill -> >=1 decode -> egress
chain with correct parent/child links plus fan-in ``batch::*`` spans.
The span-chain structure and the generated tokens must be IDENTICAL
across the two runs.  The same subprocess first pins the zero-cost
contract: with tracing off (and the flight recorder disabled) a full
request leaves zero rtrace spans and zero flight events — the hooks
are a single predicate read.

Phase 2 (fleet): a supervised 2-rank elastic fit
(``--supervise --np 1:2``) under a fixed ``host.slow`` chaos spec,
with the supervisor's aggregated ``/metrics`` endpoint armed.  While
both ranks run, the aggregated endpoint must serve BOTH ranks'
rank-labeled series plus fleet rollups.  Rank 1 SIGKILLs itself
mid-run: the supervisor signals the survivor before killing the gang,
so rank 0's flight dump lands in PADDLE_FLIGHT_DIR with its chaos
injections at EXACT counts; the supervisor's own dump carries the
rendezvous rounds; both fold into PADDLE_SUPERVISE_REPORT.  The two
per-rank chrome traces exported before the kill must merge into one
timeline with one lane per rank, clock-aligned.

Wired into tools/run_all_tests.sh.
"""
import http.client
import json
import os
import socket
import subprocess
import sys
import tempfile
import textwrap
import time

os.environ.setdefault("JAX_PLATFORMS", "cpu")
REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TID = "ab" * 16
PARENT = "12" * 8

BURST = """
import json, os, sys, threading, time
import numpy as np
import http.client
import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.profiler import flight, rtrace, tracer

TID = %(tid)r
PARENT = %(parent)r
out_path = sys.argv[1]

paddle.seed(0)
net = GPT(GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                    num_heads=2, max_seq_len=64, ffn_mult=2))
eng = serving.GenerationEngine(net, serving.GenerationEngineConfig(
    max_slots=4, max_length=64, max_new_tokens=6, name="obsgate"))

# -- zero-cost pin: tracing off + recorder off => zero events ---------
paddle.set_flags({"FLAGS_flight_recorder": 0})
flight.clear()
tracer.clear()
assert not rtrace.active
eng.generate([11, 12, 13], max_new_tokens=2, timeout=300)
assert [e for e in tracer.events() if e[4] == "rtrace"] == [], \\
    "rtrace spans recorded with tracing off"
assert flight.events() == [], "flight events recorded while disabled"
paddle.set_flags({"FLAGS_flight_recorder": 1})

# -- traced burst ------------------------------------------------------
rtrace.enable()
res = {}
with serving.ServingServer(eng) as srv:
    def other(i):
        time.sleep(0.01 * i)
        conn = http.client.HTTPConnection(srv.host, srv.port,
                                          timeout=300)
        conn.request("POST", "/v1/generate", json.dumps(
            {"prompt_ids": [20 + i, 21, 22], "max_new_tokens": 6}),
            {"Content-Type": "application/json"})
        conn.getresponse().read()
        conn.close()
    ts = [threading.Thread(target=other, args=(i,)) for i in (1, 2)]
    for t in ts:
        t.start()
    conn = http.client.HTTPConnection(srv.host, srv.port, timeout=300)
    conn.request("POST", "/v1/generate", json.dumps(
        {"prompt_ids": [3, 5, 7], "max_new_tokens": 6, "seed": 0}),
        {"Content-Type": "application/json",
         "traceparent": f"00-{TID}-{PARENT}-01",
         "X-Request-Id": "obsgate-req"})
    r = conn.getresponse()
    echoed = r.getheader("traceparent")
    rid = r.getheader("X-Request-Id")
    tokens = json.loads(r.read())["tokens"]
    conn.close()
    for t in ts:
        t.join()
eng.close()

assert r.status == 200
assert echoed.split("-")[1] == TID, f"trace_id not echoed: {echoed}"
assert rid == "obsgate-req"

spans = rtrace.request_spans(trace_id=TID)
by_name = {}
chain = []
root = None
for s in spans:
    if s["name"] == "ingress":
        root = s["span_id"]
by_name = {s["name"]: s for s in spans}
assert root is not None, "no ingress span"
assert by_name["ingress"]["parent_id"] == PARENT
for name in ("admission", "queue_wait", "prefill", "decode",
             "egress"):
    assert name in by_name, f"missing span {name}"
    assert by_name[name]["parent_id"] == root, \\
        f"{name} not parented to ingress"
assert by_name["admission"]["outcome"] == "admitted"
n_decode = sum(1 for s in spans if s["name"] == "decode")
assert n_decode >= 1
for s in spans:
    if s["name"] == "decode":
        assert "batch_span" in s
# fan-in: every decode's batch span links this trace back
links = {(e[5] or {}).get("span_id"): e[5] for e in tracer.events()
         if e[4] == "rtrace" and e[0].startswith("batch::")}
for s in spans:
    if s["name"] == "decode":
        b = links[s["batch_span"]]
        assert any(l["trace_id"] == TID for l in b["links"])

chain = [(s["name"],
          s.get("parent_id") == root or s["name"] == "ingress",
          s.get("outcome"), bool(s.get("terminated")))
         for s in spans]
with open(out_path, "w") as f:
    json.dump({"trace_id": TID, "tokens": tokens, "chain": chain,
               "n_decode": n_decode}, f)
print("burst ok:", [c[0] for c in chain])
"""

TRAINER = """
import json, os, signal
import numpy as np
import paddle_tpu as paddle
from paddle_tpu.distributed import fleet_metrics as fm
from paddle_tpu.hapi.callbacks import Callback
from paddle_tpu.profiler import tracer

gen = int(os.environ.get("PADDLE_RESTART_GENERATION", "0"))
rank = int(os.environ.get("PADDLE_TRAINER_ID", "0"))
work = os.environ["OBS_GATE_DIR"]

paddle.seed(0)
net = paddle.nn.Sequential(paddle.nn.Linear(4, 8), paddle.nn.Tanh(),
                           paddle.nn.Linear(8, 1))
model = paddle.Model(net)
opt = paddle.optimizer.Adam(1e-2, parameters=net.parameters())
model.prepare(opt, paddle.nn.MSELoss())
tracer.enable()


class DS(paddle.io.Dataset):
    def __getitem__(self, i):
        import time
        time.sleep(0.05)
        rng = np.random.RandomState(i)
        x = rng.rand(4).astype("float32")
        return x, (x.sum(keepdims=True) * 0.5).astype("float32")

    def __len__(self):
        return 64           # batch 4 -> 16 steps per epoch


class Obs(Callback):
    def on_train_batch_end(self, step, logs=None):
        if step == 4:
            # per-rank chrome trace for the supervisor-side merge
            fm.write_rank_trace(
                os.path.join(work, f"trace.r{rank}.g{gen}.json"),
                rank=rank)
        if rank == 1 and gen == 0 and step == 8:
            os.kill(os.getpid(), signal.SIGKILL)


model.fit(DS(), batch_size=4, epochs=1, verbose=0, shuffle=False,
          callbacks=[Obs()])
with open(os.path.join(work, f"done.r{rank}.g{gen}"), "w") as f:
    f.write("ok")
"""

CHAOS_SPEC = "host.slow:delay=0.01@2-3"     # exactly 2 injections/proc


def free_port():
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def run_burst(work, tag):
    script = os.path.join(work, "burst.py")
    with open(script, "w") as f:
        f.write(BURST % {"tid": TID, "parent": PARENT})
    out = os.path.join(work, f"burst.{tag}.json")
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               PADDLE_THREAD_CANARY="0")
    # run 2 is fast: both runs share JAX's persistent cache at its
    # fixed path (utils/compile_cache.py)
    r = subprocess.run([sys.executable, script, out], env=env,
                       cwd=REPO, capture_output=True, text=True,
                       timeout=900)
    if r.returncode != 0:
        print(r.stdout[-3000:], file=sys.stderr)
        print(r.stderr[-3000:], file=sys.stderr)
        raise SystemExit(f"obs gate: traced burst {tag} failed "
                         f"(rc={r.returncode})")
    return json.load(open(out))


def scrape_both_ranks(port, proc, deadline_s=120):
    """Poll the aggregated /metrics until both ranks' labeled series
    appear (they publish on the heartbeat cadence)."""
    t0 = time.monotonic()
    last = ""
    while time.monotonic() - t0 < deadline_s and proc.poll() is None:
        try:
            conn = http.client.HTTPConnection("127.0.0.1", port,
                                              timeout=5)
            conn.request("GET", "/metrics")
            r = conn.getresponse()
            ctype = r.getheader("Content-Type")
            last = r.read().decode()
            conn.close()
            if 'rank="0"' in last and 'rank="1"' in last:
                assert ctype == "text/plain; version=0.0.4", ctype
                return last
        except (OSError, http.client.HTTPException):
            pass
        time.sleep(0.05)
    raise SystemExit(
        "obs gate: aggregated /metrics never showed both ranks "
        f"(supervisor rc={proc.poll()}); last scrape:\n{last[-2000:]}")


def main():
    work = tempfile.mkdtemp(prefix="obs_gate_")

    # -- phase 1: traced burst, twice, bit-stable ----------------------
    a = run_burst(work, "run1")
    b = run_burst(work, "run2")
    assert a["chain"] == b["chain"], \
        f"span chains differ across runs:\n{a['chain']}\n{b['chain']}"
    assert a["tokens"] == b["tokens"], "tokens differ across runs"
    assert a["n_decode"] >= 1
    names = [c[0] for c in a["chain"]]
    assert names[0] == "ingress" and names[-1] == "egress"

    # -- phase 2: supervised 2-rank fleet ------------------------------
    trainer = os.path.join(work, "trainer.py")
    with open(trainer, "w") as f:
        f.write(textwrap.dedent(TRAINER))
    report = os.path.join(work, "report.json")
    flight_dir = os.path.join(work, "flight")
    port = free_port()
    env = dict(os.environ,
               JAX_PLATFORMS="cpu", PYTHONPATH=REPO,
               FLAGS_chaos_spec=CHAOS_SPEC,
               FLAGS_straggler_factor="0",   # equal delays != straggler
               OBS_GATE_DIR=work,
               PADDLE_HEARTBEAT_INTERVAL="0.05",
               PADDLE_SUPERVISE_REPORT=report,
               PADDLE_FLIGHT_DIR=flight_dir,
               PADDLE_FLEET_METRICS_PORT=str(port))
    proc = subprocess.Popen(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--supervise", "--np", "1:2", "--nproc", "2",
         "--max_restarts", "1", trainer],
        env=env, cwd=REPO, stdout=subprocess.PIPE,
        stderr=subprocess.PIPE, text=True)
    try:
        agg = scrape_both_ranks(port, proc)
        out, err = proc.communicate(timeout=600)
    except BaseException:
        proc.kill()
        out, err = proc.communicate()
        print(out[-3000:], file=sys.stderr)
        print(err[-3000:], file=sys.stderr)
        raise
    if proc.returncode != 0:
        print(out[-3000:], file=sys.stderr)
        print(err[-3000:], file=sys.stderr)
        raise SystemExit(f"obs gate: supervised launch failed "
                         f"(rc={proc.returncode})")

    # aggregated rollup present (both ranks' hapi series + fleet stat)
    assert "_fleet{stat=" in agg or "_fleet_count{stat=" in agg, \
        "no fleet rollup series in aggregated /metrics"

    rep = json.load(open(report))
    assert rep["kind"] == "done", rep
    # SIGKILL == host loss: one shrink (2 -> 1), no budget spent
    assert rep["shrinks"] == 1 and rep["restarts"] == 0, rep
    assert rep["world_history"] == [2, 1], rep

    dumps = rep["flight_dumps"]
    # the survivor (rank 0, generation 0) dumped on the pre-kill
    # SIGUSR1 — its tail must hold the chaos injections, exact count
    surv = dumps.get("flight.r0.g0.json")
    assert surv is not None, f"no survivor flight dump: {list(dumps)}"
    assert surv["events"] > 0, surv
    assert surv["counts"].get("chaos.host.slow") == 2, surv["counts"]
    assert surv["counts"].get("launch.fit_start") == 1, surv["counts"]
    # the supervisor's own dump: one rendezvous note per gang
    # formation, matching the report's counter
    sup = dumps.get("flight.supervisor.json")
    assert sup is not None, f"no supervisor flight dump: {list(dumps)}"
    assert sup["counts"].get("launch.rendezvous") == \
        rep["rendezvous_rounds"] == 2, (sup["counts"], rep)

    # merged rank-laned timeline from the pre-kill per-rank traces
    from paddle_tpu.distributed import fleet_metrics as fm
    docs = []
    for r in (0, 1):
        p = os.path.join(work, f"trace.r{r}.g0.json")
        assert os.path.exists(p), f"rank {r} never exported its trace"
        docs.append(json.load(open(p)))
    merged = fm.merge_chrome_traces(docs)
    lanes = {e["pid"] for e in merged["traceEvents"]
             if e.get("ph") == "X"}
    assert lanes == {0, 1}, f"expected one lane per rank, got {lanes}"
    assert merged["metadata"]["aligned"] is True

    print(f"obs gate OK: chain={names}, tokens={a['tokens']}, "
          f"shrinks={rep['shrinks']}, "
          f"rendezvous={rep['rendezvous_rounds']}, "
          f"survivor_dump={surv['events']} events "
          f"(chaos.host.slow={surv['counts']['chaos.host.slow']}), "
          f"merged lanes={sorted(lanes)}")


if __name__ == "__main__":
    main()
