"""End-to-end request tracing, fleet aggregation, flight recorder.

Acceptance surface (ISSUE 12):

- a request sent with a W3C ``traceparent`` header gets the SAME
  trace_id echoed back, and its exported trace carries a complete
  ingress -> admission -> queue_wait -> prefill -> decode... -> egress
  span chain with correct parent/child links;
- a batch step emits ONE span linked to every member request (fan-in
  causality) — batchmates share the linked span;
- a rejected/shed request still gets a terminated span carrying the
  reject reason;
- ``X-Request-Id`` is honored on ingress, generated when absent, and
  echoed on every response — including SSE terminal events and error
  payloads;
- registry histograms export Prometheus ``_bucket{le=...}`` series
  (cumulative, ``+Inf`` == count) and ``/metrics`` answers with
  ``text/plain; version=0.0.4``;
- the paged engine's ``/healthz`` reports block-pool occupancy and
  prefix-cache hit rate;
- the flight recorder keeps a bounded ring of structured events,
  costs nothing when disabled, and dumps JSON on demand;
- fleet aggregation merges per-rank snapshots into rank-labeled
  Prometheus series with min/max/sum rollups, and per-rank chrome
  traces merge into one rank-laned, clock-aligned timeline.
"""
import http.client
import json
import threading
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.profiler import flight, metrics, rtrace, tracer

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                num_heads=2, max_seq_len=64, ffn_mult=2)


def val(name):
    m = metrics.get(name)
    return m.value if m is not None else 0


@pytest.fixture(scope="module")
def net():
    paddle.seed(0)
    return GPT(CFG)


@pytest.fixture()
def traced():
    """rtrace armed over a clean tracer ring; restored on exit."""
    tracer.clear()
    rtrace.enable()
    yield
    rtrace.disable()
    tracer.clear()


def make_engine(net, name, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_new_tokens", 8)
    return serving.GenerationEngine(
        net, serving.GenerationEngineConfig(name=name, **kw))


def _post(conn, path, body, headers=None):
    h = {"Content-Type": "application/json"}
    h.update(headers or {})
    conn.request("POST", path, json.dumps(body), h)
    return conn.getresponse()


# ---------------------------------------------------------------------------
# traceparent / TraceContext unit surface
# ---------------------------------------------------------------------------

def test_traceparent_parse_and_echo():
    tid, sid = "ab" * 16, "12" * 8
    parsed = rtrace.parse_traceparent(f"00-{tid}-{sid}-01")
    assert parsed == (tid, sid)
    # malformed / all-zero / bad-version headers start a fresh trace
    for bad in (None, "", "garbage", f"00-{'0' * 32}-{sid}-01",
                f"00-{tid}-{'0' * 16}-01", f"ff-{tid}-{sid}-01",
                f"00-{tid[:-2]}-{sid}-01"):
        assert rtrace.parse_traceparent(bad) is None
        ctx = rtrace.TraceContext.from_headers(bad, request_id="r")
        assert len(ctx.trace_id) == 32 and ctx.parent_id is None
    ctx = rtrace.TraceContext.from_headers(f"00-{tid}-{sid}-01")
    assert ctx.trace_id == tid and ctx.parent_id == sid
    echoed = ctx.traceparent()
    assert echoed.startswith(f"00-{tid}-") and echoed.endswith("-01")
    assert ctx.root in echoed


def test_rtrace_zero_cost_when_disabled(net):
    """Tracing off: a request leaves NO rtrace spans (the engine hops
    gate on one module predicate — the PR 1 discipline)."""
    assert not rtrace.active
    tracer.clear()
    with make_engine(net, "obs_off") as eng:
        eng.generate([3, 5, 7], max_new_tokens=2, timeout=120)
    assert [e for e in tracer.events() if e[4] == "rtrace"] == []


# ---------------------------------------------------------------------------
# span chains over the HTTP + continuous-batching path
# ---------------------------------------------------------------------------

def test_staggered_clients_complete_span_chains(net, traced):
    """3 staggered clients: every admitted request yields a complete
    ingress->egress chain under its own trace_id, decode work is
    accounted through batch spans that link the batchmates, and the
    traceparent a client sent comes back with its trace_id."""
    tids = ["%032x" % (0xA0 + i) for i in range(3)]
    results = {}
    with make_engine(net, "obs_stag") as eng:
        with serving.ServingServer(eng) as srv:
            def client(i):
                time.sleep(0.03 * i)       # staggered arrivals
                conn = http.client.HTTPConnection(srv.host, srv.port,
                                                  timeout=120)
                r = _post(conn, "/v1/generate",
                          {"prompt_ids": [3 + i, 5, 7],
                           "max_new_tokens": 6, "seed": i},
                          {"traceparent": f"00-{tids[i]}-{'12' * 8}-01",
                           "X-Request-Id": f"req-{i}"})
                results[i] = (r.status, r.getheader("traceparent"),
                              r.getheader("X-Request-Id"),
                              json.loads(r.read()))
                conn.close()
            ts = [threading.Thread(target=client, args=(i,))
                  for i in range(3)]
            for t in ts:
                t.start()
            for t in ts:
                t.join()
    evs = tracer.events()
    for i in range(3):
        status, tp, rid, body = results[i]
        assert status == 200 and len(body["tokens"]) == 6
        assert tp.split("-")[1] == tids[i]      # same trace_id back
        assert rid == f"req-{i}"
        spans = rtrace.request_spans(evs, trace_id=tids[i])
        names = [s["name"] for s in spans]
        for required in ("ingress", "admission", "queue_wait",
                         "prefill", "decode", "egress"):
            assert required in names, (i, names)
        assert names.count("decode") >= 1
        by_name = {s["name"]: s for s in spans}
        root = by_name["ingress"]["span_id"]
        # parent/child links: ingress is the root (parented to the
        # CLIENT's span), every other span is its child
        assert by_name["ingress"]["parent_id"] == "12" * 8
        for n in ("admission", "queue_wait", "prefill", "decode",
                  "egress"):
            assert by_name[n]["parent_id"] == root, n
        assert by_name["admission"]["outcome"] == "admitted"
        # every span carries the request id
        assert all(s.get("request_id") == f"req-{i}" for s in spans)
        # decode spans point at their fused batch span
        assert all("batch_span" in s for s in spans
                   if s["name"] == "decode")
    # fan-in causality: with 3 staggered clients over 4 slots at least
    # one fused decode boundary must have carried >= 2 of our requests
    batch = [e[5] for e in evs
             if e[4] == "rtrace" and e[5] and e[5].get("links")
             and e[0] == "batch::decode"]
    assert batch, "no batch::decode spans recorded"
    assert any(len({ln["trace_id"] for ln in b["links"]
                    if ln["trace_id"] in tids}) >= 2 for b in batch), \
        "no decode boundary linked two staggered clients"
    # each request's decode spans name a batch span that links it back
    bids = {b.get("span_id"): b for b in
            [e[5] for e in evs if e[4] == "rtrace" and e[5]
             and e[0] == "batch::decode"]}
    for i in range(3):
        for s in rtrace.request_spans(evs, trace_id=tids[i]):
            if s["name"] != "decode":
                continue
            b = bids[s["batch_span"]]
            assert any(ln["trace_id"] == tids[i] for ln in b["links"])


def test_rejected_request_gets_terminated_span(net, traced):
    """A shed request still leaves a terminated span carrying the
    reject reason — and the 429 payload carries the request id."""
    with make_engine(net, "obs_shed", max_queue=1) as eng:
        eng.pause()
        parked = eng.submit([3, 5], max_new_tokens=2)
        with serving.ServingServer(eng) as srv:
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=120)
            tid = "%032x" % 0xBEEF
            r = _post(conn, "/v1/generate", {"prompt_ids": [4, 6]},
                      {"traceparent": f"00-{tid}-{'34' * 8}-01",
                       "X-Request-Id": "shed-me"})
            assert r.status == 429
            body = json.loads(r.read())
            assert body["reason"] == "queue_full"
            assert body["request_id"] == "shed-me"
            assert r.getheader("X-Request-Id") == "shed-me"
            conn.close()
        eng.resume()
        parked.result(timeout=120)
        spans = rtrace.request_spans(trace_id=tid)
        adm = [s for s in spans if s["name"] == "admission"]
        assert adm and adm[0]["outcome"] == "queue_full"
        assert adm[0]["terminated"] is True
        names = [s["name"] for s in spans]
        assert "ingress" in names and "egress" in names


def test_request_id_generated_and_echoed_on_sse(net, traced):
    """No X-Request-Id sent -> one is generated; SSE terminal events
    carry it in-band (headers don't survive every proxy)."""
    with make_engine(net, "obs_sse") as eng:
        with serving.ServingServer(eng) as srv:
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=120)
            r = _post(conn, "/v1/generate",
                      {"prompt_ids": [3, 5, 7], "max_new_tokens": 3,
                       "stream": True})
            assert r.status == 200
            rid = r.getheader("X-Request-Id")
            assert rid                        # generated when absent
            events = [json.loads(ln[6:]) for ln in
                      r.read().decode().split("\n")
                      if ln.startswith("data: ")]
            final = [e for e in events if e.get("done")][0]
            assert final["request_id"] == rid
            # malformed payload: error body carries the id too
            conn.request("POST", "/v1/generate", "{}",
                         {"Content-Type": "application/json",
                          "X-Request-Id": "err-1"})
            r = conn.getresponse()
            assert r.status == 400
            assert json.loads(r.read())["request_id"] == "err-1"
            conn.close()


# ---------------------------------------------------------------------------
# Prometheus conformance + /healthz occupancy
# ---------------------------------------------------------------------------

def test_histogram_bucket_series():
    h = metrics.Histogram("obs_lat_ms")
    for v in (0.3, 3.0, 40.0, 99.0, 1e6):
        h.observe(v)
    pairs = h.bucket_counts()
    assert pairs[-1] == ("+Inf", 5)           # +Inf == count
    d = dict(pairs)
    assert d["0.5"] == 1 and d["5"] == 2 and d["50"] == 3
    assert d["100"] == 4                      # 99 <= le=100
    cums = [c for _le, c in pairs]
    assert cums == sorted(cums)               # cumulative, monotone


def test_prometheus_text_histogram_conformance():
    reg = metrics.Registry()
    h = reg.histogram("obs_req_ms")
    h.observe(2.0)
    h.observe(80.0)
    reg.counter("obs_total").inc(3)
    text = reg.to_prometheus()
    assert "# TYPE obs_req_ms histogram" in text
    assert 'obs_req_ms_bucket{le="2.5"} 1' in text
    assert 'obs_req_ms_bucket{le="100"} 2' in text
    assert 'obs_req_ms_bucket{le="+Inf"} 2' in text
    assert "obs_req_ms_sum 82.0" in text
    assert "obs_req_ms_count 2" in text
    assert "# TYPE obs_total counter" in text


def test_metrics_endpoint_content_type(net):
    with make_engine(net, "obs_ct") as eng:
        with serving.ServingServer(eng) as srv:
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=120)
            conn.request("GET", "/metrics")
            r = conn.getresponse()
            assert r.status == 200
            assert r.getheader("Content-Type") == \
                "text/plain; version=0.0.4"
            body = r.read().decode()
            assert "_bucket{le=" in body
            conn.close()


def test_paged_healthz_reports_block_pool(net):
    eng = serving.PagedGenerationEngine(
        net, serving.GenerationEngineConfig(
            max_slots=2, max_length=64, max_new_tokens=4,
            block_size=16, prefix_cache_blocks=8, name="obs_paged"))
    try:
        with serving.ServingServer(eng) as srv:
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=120)
            r = _post(conn, "/v1/generate",
                      {"prompt_ids": [3, 5, 7, 9], "max_new_tokens": 4})
            assert r.status == 200
            r.read()
            # same prompt again: prefix-cache hit
            r = _post(conn, "/v1/generate",
                      {"prompt_ids": [3, 5, 7, 9], "max_new_tokens": 4})
            assert r.status == 200
            r.read()
            conn.request("GET", "/healthz")
            h = json.loads(conn.getresponse().read())
            conn.close()
        assert h["kv_blocks_total"] == eng.pool.num_blocks
        assert h["kv_blocks_in_flight"] + h["kv_blocks_free"] == \
            h["kv_blocks_total"]
        assert h["kv_block_size"] == 16
        assert 0.0 < h["prefix_cache_hit_rate"] <= 1.0
    finally:
        eng.close()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def test_flight_ring_bounded_counts_and_dump(tmp_path):
    flight.clear()
    assert flight.active                      # always-on by default
    for i in range(5):
        flight.note("test", "ping", i=i)
    flight.note("test", "pong")
    assert flight.counts() == {"test.ping": 5, "test.pong": 1}
    # capacity bound: oldest events drop
    paddle.set_flags({"FLAGS_flight_recorder_capacity": 4})
    try:
        for i in range(10):
            flight.note("test", "burst", i=i)
        evs = flight.events()
        assert len(evs) == 4
        assert evs[-1][3] == {"i": 9}
    finally:
        paddle.set_flags({"FLAGS_flight_recorder_capacity": 2048})
    p = tmp_path / "flight.json"
    doc = flight.dump(str(p), reason="test")
    on_disk = json.loads(p.read_text())
    assert on_disk["reason"] == "test"
    assert [e["event"] for e in on_disk["events"]] == ["burst"] * 4
    assert doc["counts"] == {"test.burst": 4}
    flight.clear()


def test_flight_disabled_costs_one_predicate(net):
    """FLAGS_flight_recorder=0: sites skip entirely — an engine
    round-trip leaves the ring untouched."""
    paddle.set_flags({"FLAGS_flight_recorder": 0})
    try:
        assert not flight.active
        flight.clear()
        with make_engine(net, "obs_foff") as eng:
            eng.generate([3, 5], max_new_tokens=2, timeout=120)
        assert flight.events() == []
    finally:
        paddle.set_flags({"FLAGS_flight_recorder": 1})
    assert flight.active


def test_flight_records_serving_lifecycle(net):
    flight.clear()
    with make_engine(net, "obs_flt") as eng:
        eng.generate([3, 5, 7], max_new_tokens=2, timeout=120)
    c = flight.counts()
    assert c.get("admission.admit", 0) >= 1
    assert c.get("serve.slot_admit", 0) >= 1
    assert c.get("serve.slot_retire", 0) >= 1
    retire = [e for e in flight.events()
              if e[1] == "serve" and e[2] == "slot_retire"]
    assert retire[-1][3]["reason"] == "max_new_tokens"
    flight.clear()


def test_flight_records_chaos_injection():
    from paddle_tpu.utils import chaos
    flight.clear()
    paddle.set_flags({"FLAGS_chaos_spec": "host.slow:delay=0.0@1-2"})
    try:
        chaos.hit("host.slow")
        chaos.hit("host.slow")
        chaos.hit("host.slow")                # past the window
    finally:
        paddle.set_flags({"FLAGS_chaos_spec": ""})
    assert flight.counts().get("chaos.host.slow") == 2
    flight.clear()


# ---------------------------------------------------------------------------
# fleet aggregation + trace merge
# ---------------------------------------------------------------------------

def _payload(rank, metrics_dict, perf_ns, unix):
    return {"rank": str(rank), "step": 1,
            "clock": {"perf_ns": perf_ns, "unix": unix},
            "metrics": metrics_dict}


def test_aggregate_prometheus_rank_labels_and_rollups():
    from paddle_tpu.distributed import fleet_metrics as fm
    per_rank = {
        "0": _payload(0, {"train.loss": 1.5,
                          "hapi.train_step_latency_ms":
                          {"count": 10, "sum": 120.0, "p50": 11.0}},
                      0, 0.0),
        "1": _payload(1, {"train.loss": 2.5,
                          "hapi.train_step_latency_ms":
                          {"count": 8, "sum": 100.0, "p50": 13.0}},
                      0, 0.0),
    }
    text = fm.aggregate_prometheus(per_rank)
    assert 'train_loss{rank="0"} 1.5' in text
    assert 'train_loss{rank="1"} 2.5' in text
    assert 'train_loss_fleet{stat="min"} 1.5' in text
    assert 'train_loss_fleet{stat="max"} 2.5' in text
    assert 'train_loss_fleet{stat="sum"} 4.0' in text
    assert 'hapi_train_step_latency_ms_count{rank="0"} 10' in text
    assert 'hapi_train_step_latency_ms_fleet_count{stat="sum"} 18.0' \
        in text
    assert 'quantile="0.50"' in text


def test_fleet_publish_collect_roundtrip():
    from paddle_tpu.distributed import fleet_metrics as fm

    class FakeStore:
        def __init__(self):
            self.kv = {}

        def put(self, k, v, ttl=None):
            self.kv[k] = v

        def list_prefix(self, pfx):
            return {k: v for k, v in self.kv.items()
                    if k.startswith(pfx)}

    store = FakeStore()
    fm.publish(store, "jobX", 0, 0, step=7,
               snapshot={"train.loss": 0.5})
    fm.publish(store, "jobX", 0, 1, step=7,
               snapshot={"train.loss": 0.7})
    fm.publish(store, "jobX", 1, 0, step=9,
               snapshot={"train.loss": 0.1})
    got = fm.collect(store, "jobX", 0)
    assert sorted(got) == ["0", "1"]
    assert got["0"]["metrics"]["train.loss"] == 0.5
    assert got["0"]["step"] == 7
    # generation fencing: g1 only sees its own ranks
    assert sorted(fm.collect(store, "jobX", 1)) == ["0"]
    # torn payloads are skipped, not fatal
    store.kv[fm.metrics_key("jobX", 0, 2)] = "{not json"
    assert sorted(fm.collect(store, "jobX", 0)) == ["0", "1"]


def test_merge_chrome_traces_rank_lanes_and_alignment():
    from paddle_tpu.distributed import fleet_metrics as fm

    def doc(rank, ts_us, clock="unix"):
        return {"traceEvents": [
            {"name": f"step_r{rank}", "ph": "X", "ts": ts_us,
             "dur": 5.0, "pid": 4242, "tid": 1, "cat": "hapi"}],
            "displayTimeUnit": "ms",
            "metadata": {"rank": str(rank), "clock": clock}}

    # exported ``ts`` is on the unix axis already: no lane is shifted
    merged = fm.merge_chrome_traces([doc(0, 5e15 + 100.0),
                                     doc(1, 5e15 + 130.0)])
    evs = [e for e in merged["traceEvents"] if e.get("ph") == "X"]
    pids = {e["pid"] for e in evs}
    assert pids == {0, 1}                     # one lane per rank
    lanes = {e["pid"]: e["ts"] for e in evs}
    assert lanes == {0: 0.0, 1: 30.0}         # rebased, order and gap kept
    names = {e["args"]["name"] for e in merged["traceEvents"]
             if e.get("ph") == "M"}
    assert names == {"rank 0", "rank 1"}
    assert merged["metadata"]["aligned"] is True
    # a doc on some other clock (a perf_counter pair from before the
    # export moved to the epoch) keeps its lane, and the result says so
    old = doc(2, 100.0, clock={"perf_ns": 1, "unix": 2.0})
    merged = fm.merge_chrome_traces([doc(0, 5e15), old])
    assert {e["pid"] for e in merged["traceEvents"]} == {0, 2}
    assert merged["metadata"]["aligned"] is False


def test_write_rank_trace_carries_clock(tmp_path):
    from paddle_tpu.distributed import fleet_metrics as fm
    tracer.enable()
    t0 = tracer.now_ns()
    before = time.time()
    tracer.record("obs::probe", t0, t0 + 1000)
    path = fm.write_rank_trace(str(tmp_path / "t.json"), rank=3)
    tracer.disable()
    tracer.clear()
    doc = json.loads(open(path).read())
    assert doc["metadata"] == {"rank": "3", "clock": "unix"}
    probe, = [e for e in doc["traceEvents"] if e["name"] == "obs::probe"]
    assert abs(probe["ts"] / 1e6 - before) < 5.0      # us since the epoch


def test_rank_traces_of_two_hosts_merge_aligned(tmp_path, monkeypatch):
    """Two hosts' ``perf_counter`` origins differ by their uptimes; what
    ``write_rank_trace`` exports is on the epoch clock, so spans of one
    wall-clock instant land at one ``ts`` after the merge, and launch
    spans (epoch from the start) beside them."""
    from paddle_tpu.distributed import fleet_metrics as fm
    docs = []
    t0 = tracer.now_ns()
    # rank 1's host has been up 1000 s longer: the same instant reads
    # 1000 s more on its perf_counter, and its offset to the epoch is
    # 1000 s less
    for rank, uptime_ns in ((0, 0), (1, int(1000e9))):
        monkeypatch.setattr(tracer, "EPOCH_OFFSET_NS",
                            tracer.EPOCH_OFFSET_NS - uptime_ns)
        path = fm.write_rank_trace(
            str(tmp_path / f"r{rank}.json"), rank=rank,
            events=[("step", t0 + uptime_ns + rank * 7000,
                     t0 + uptime_ns + rank * 7000 + 5000, 1, "hapi", None)])
        monkeypatch.undo()
        docs.append(json.loads(open(path).read()))
    merged = fm.merge_chrome_traces(docs)
    ts = {e["pid"]: e["ts"] for e in merged["traceEvents"]
          if e.get("ph") == "X"}
    # float64 us at 1.7e15 resolve to a quarter of a microsecond
    assert ts[0] == 0.0 and abs(ts[1] - 7.0) < 0.5
    assert merged["metadata"]["aligned"] is True


def test_fleet_metrics_server_end_to_end():
    """Store -> publish (2 ranks) -> FleetMetricsServer /metrics with
    rank labels + conformant content type, /fleet JSON companion."""
    from paddle_tpu.distributed import fleet_metrics as fm
    from paddle_tpu.distributed.fleet.elastic.manager import KVServer
    kv = KVServer().start()
    try:
        spec = f"tcp://{kv.endpoint}"
        from paddle_tpu.distributed.fleet.elastic.manager import \
            store_from_spec
        store = store_from_spec(spec)
        fm.publish(store, "jobS", 0, 0, snapshot={"serving.qps": 10})
        fm.publish(store, "jobS", 0, 1, snapshot={"serving.qps": 30})
        srv = fm.FleetMetricsServer(spec, "jobS", lambda: 0).start()
        try:
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=60)
            conn.request("GET", "/metrics")
            r = conn.getresponse()
            assert r.status == 200
            assert r.getheader("Content-Type") == \
                "text/plain; version=0.0.4"
            text = r.read().decode()
            assert 'serving_qps{rank="0"} 10' in text
            assert 'serving_qps{rank="1"} 30' in text
            assert 'serving_qps_fleet{stat="sum"} 40' in text
            conn.request("GET", "/fleet")
            r = conn.getresponse()
            fleet = json.loads(r.read())
            assert sorted(fleet) == ["0", "1"]
            conn.close()
        finally:
            srv.stop()
    finally:
        kv.stop()


# ---------------------------------------------------------------------------
# waterfall CLI
# ---------------------------------------------------------------------------

def test_trace_summary_request_waterfall(net, traced, tmp_path):
    import sys
    sys.path.insert(0, "tools")
    try:
        import trace_summary as ts
    finally:
        sys.path.pop(0)
    tid = "%032x" % 0xFACE
    with make_engine(net, "obs_wf") as eng:
        with serving.ServingServer(eng) as srv:
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=120)
            r = _post(conn, "/v1/generate",
                      {"prompt_ids": [3, 5, 7], "max_new_tokens": 3},
                      {"traceparent": f"00-{tid}-{'56' * 8}-01",
                       "X-Request-Id": "wf-1"})
            assert r.status == 200
            r.read()
            conn.close()
    path = tmp_path / "trace.json"
    tracer.export_chrome_tracing(str(path))
    with open(path) as f:
        events = json.load(f)["traceEvents"]
    spans = ts.request_spans(events, tid)
    names = [e["name"] for e in spans]
    for required in ("ingress", "admission", "prefill", "egress"):
        assert required in names
    assert any(n.startswith("batch::") for n in names)  # linked folds in
    out = ts.format_waterfall(spans, tid)
    assert "ingress" in out and "wf-1" in out
    # request-id lookup works too
    assert ts.request_spans(events, "wf-1")
