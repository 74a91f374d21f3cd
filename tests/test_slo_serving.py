"""Multi-tenant SLO serving (PR 18).

Acceptance surface:

- **priority dequeue with aging** — interactive requests dequeue ahead
  of batch, yet a batch request climbs one class per ``aging_s``
  queued so it cannot starve forever;
- **token buckets** — per-tenant quota refill is deterministic under a
  frozen clock; exhaustion sheds typed ``tenant_quota`` with a
  drain-rate-derived Retry-After; the table hot-reloads from a JSON
  file (:class:`QuotaWatcher`) without a restart;
- **preempt -> resume bit-exactness** — a batch stream preempted to
  host memory under block-pool pressure resumes bit-identical to its
  unpreempted reference (greedy AND sampled), its SSE consumer seeing
  one seamless token sequence;
- **deadline across preemption** — a parked request whose deadline
  expires while swapped out sheds with typed ``deadline_preempted``
  (releasing the host-side state) instead of resuming for nobody;
- **Retry-After** — 429 sheds carry the drain-rate-derived hint,
  clamped to [1, 30] s, over HTTP too.
"""
import json
import time

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu import serving
from paddle_tpu.models import GPT, GPTConfig
from paddle_tpu.profiler import flight, metrics
from paddle_tpu.serving.admission import (AdmissionController,
                                          DrainRateEstimator,
                                          QuotaWatcher, RequestRejected,
                                          TenantQuotaTable,
                                          priority_rank)

CFG = GPTConfig(vocab_size=97, hidden_size=32, num_layers=2,
                num_heads=2, max_seq_len=64, ffn_mult=2)
BS = 16                                  # block_size; divides 64


def val(name):
    m = metrics.get(name)
    return m.value if m is not None else 0


@pytest.fixture(scope="module")
def net():
    paddle.seed(0)
    return GPT(CFG)


def paged_engine(net, name, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("max_length", 64)
    kw.setdefault("max_new_tokens", 8)
    kw.setdefault("block_size", BS)
    kw.setdefault("warmup", "off")
    return serving.PagedGenerationEngine(
        net, serving.GenerationEngineConfig(name=name, **kw))


# -- priority classes --------------------------------------------------

def _admissions(eng, names):
    """The order in which the scheduler admits requests to a decode slot,
    as names by the first prompt token — read where the engine notes the
    admission, not from the order in which waiting threads wake up."""
    order, note = [], eng._note_slot_admit

    def record(slot, req):
        order.append(names[int(req.prompt[0])])
        note(slot, req)
    eng._note_slot_admit = record
    return order


def test_priority_rank_mapping():
    assert priority_rank("interactive") == 0
    assert priority_rank("standard") == 1
    assert priority_rank("batch") == 2
    assert priority_rank(None) == 1       # default class
    with pytest.raises(ValueError):
        priority_rank("vip")              # typo'd header must 400


def test_priority_dequeue_order(net):
    """With the engine paused, queue batch then interactive then
    standard: un-pausing must admit interactive first, then standard,
    then batch — regardless of arrival order."""
    eng = paged_engine(net, "tsp_order", max_slots=1, num_blocks=4,
                       prefix_cache_blocks=0, aging_s=0.0)
    try:
        eng.pause()
        p = np.arange(1, 6, dtype=np.int32)
        order = _admissions(eng, {1: "batch", 2: "interactive",
                                  3: "standard"})
        sb = eng.submit(p, max_new_tokens=2, priority="batch")
        si = eng.submit(p + 1, max_new_tokens=2, priority="interactive")
        ss = eng.submit(p + 2, max_new_tokens=2)   # standard default
        eng.resume()
        for stream in (sb, si, ss):
            stream.result(timeout=60)
        assert order == ["interactive", "standard", "batch"]
    finally:
        eng.close()


def test_priority_aging_prevents_starvation(net):
    """A batch request that has waited >= 2*aging_s outranks a fresh
    interactive request: bounded aging, not strict starvation."""
    eng = paged_engine(net, "tsp_aging", max_slots=1, num_blocks=4,
                       prefix_cache_blocks=0, aging_s=0.05)
    try:
        eng.pause()
        p = np.arange(1, 6, dtype=np.int32)
        sb = eng.submit(p, max_new_tokens=2, priority="batch")
        time.sleep(0.15)                  # batch ages >= 2 classes
        si = eng.submit(p + 1, max_new_tokens=2, priority="interactive")
        order = _admissions(eng, {1: "batch", 2: "interactive"})
        eng.resume()
        for stream in (sb, si):
            stream.result(timeout=60)
        assert order[0] == "batch"        # aged past the fresh burst
    finally:
        eng.close()


# -- token buckets -----------------------------------------------------

def test_token_bucket_frozen_clock_determinism():
    now = [100.0]
    table = TenantQuotaTable({"acme": {"rate": 10.0, "burst": 30.0}},
                             clock=lambda: now[0])
    assert table.try_acquire("acme", 30)          # drain the burst
    assert not table.try_acquire("acme", 1)       # empty, no time passed
    now[0] += 1.0                                 # +10 tokens exactly
    assert table.level("acme") == pytest.approx(10.0)
    assert table.try_acquire("acme", 10)
    assert not table.try_acquire("acme", 1)
    now[0] += 100.0                               # refill clamps at burst
    assert table.level("acme") == pytest.approx(30.0)


def test_token_bucket_default_and_unlimited():
    now = [0.0]
    table = TenantQuotaTable({"*": {"rate": 1.0, "burst": 2.0}},
                             clock=lambda: now[0])
    assert table.try_acquire("anyone", 2)
    assert not table.try_acquire("anyone", 1)     # "*" applies
    unlimited = TenantQuotaTable({"paid": {"rate": 1.0}},
                                 clock=lambda: now[0])
    assert unlimited.try_acquire("other", 10 ** 6)  # no "*": unlimited


def test_quota_reload_atomic_and_validated():
    now = [0.0]
    table = TenantQuotaTable({"a": {"rate": 5.0, "burst": 10.0}},
                             clock=lambda: now[0])
    assert table.try_acquire("a", 8)              # level -> 2
    gen = table.generation
    with pytest.raises(ValueError):
        table.reload({"a": {"rate": -1}})         # rejected whole
    assert table.generation == gen                # nothing applied
    table.reload({"a": {"rate": 5.0, "burst": 1.0}})
    assert table.level("a") <= 1.0                # clamped to new burst


def test_tenant_quota_rejects_typed():
    ctl = AdmissionController(
        8, name="tsp_quota",
        quotas=TenantQuotaTable({"free": {"rate": 0.0, "burst": 4.0}}))
    ctl.acquire(tenant="free", priority="standard", quota_tokens=4)
    ctl.release()
    with pytest.raises(RequestRejected) as ei:
        ctl.acquire(tenant="free", priority="standard", quota_tokens=4)
    assert ei.value.reason == "tenant_quota"
    assert 1 <= ei.value.retry_after <= 30
    assert val("tsp_quota.tenant.free.shed") == 1
    assert val("tsp_quota.request.rejected.tenant_quota") == 1


def test_quota_watcher_hot_reload(tmp_path):
    ctl = AdmissionController(8, name="tsp_watch")
    path = tmp_path / "quotas.json"
    path.write_text(json.dumps({"t1": {"rate": 0.0, "burst": 2.0}}))
    w = QuotaWatcher(str(path), ctl, interval=0.05)
    assert w.poll_once()
    assert ctl.quotas.limit_for("t1")["burst"] == 2.0
    # malformed edit: rejected loudly, previous table keeps serving
    path.write_text("{not json")
    with pytest.warns(RuntimeWarning):
        assert not w.poll_once()
    assert ctl.quotas.limit_for("t1")["burst"] == 2.0
    # healthy edit applies on the next poll
    time.sleep(0.01)                     # distinct mtime_ns
    path.write_text(json.dumps({"t1": {"rate": 9.0, "burst": 99.0}}))
    assert w.poll_once()
    assert ctl.quotas.limit_for("t1")["burst"] == 99.0
    assert not w.poll_once()             # unchanged file: no-op


# -- drain-rate Retry-After --------------------------------------------

def test_drain_rate_retry_after_clamped():
    now = [0.0]
    d = DrainRateEstimator(window_s=30.0, clock=lambda: now[0])
    assert d.retry_after_s(0) == 1        # empty queue: floor
    assert d.retry_after_s(5) == 30       # cold estimator: ceiling
    for _ in range(10):                   # 10 drains over 5 s = 2/s
        d.note()
        now[0] += 0.5
    assert d.rate() == pytest.approx(2.0, rel=1e-6)
    assert d.retry_after_s(4) == 2        # ceil(4 / 2)
    assert d.retry_after_s(1000) == 30    # clamped to the ceiling
    now[0] += 100.0                       # window empties -> cold again
    assert d.retry_after_s(5) == 30


# -- preemption to host memory -----------------------------------------

def preempt_scenario(net, name, do_sample):
    """Run request A (batch) on a 3-block pool, force a preemption by
    bursting an interactive request that needs 3 blocks, and return
    (reference stream, observed stream, interactive result)."""
    pA = np.arange(1, 9, dtype=np.int32)      # 1 block at prefill
    pB = np.arange(1, 41, dtype=np.int32)     # needs 3 blocks
    kwA = dict(max_new_tokens=30, do_sample=do_sample, seed=7)
    if do_sample:
        kwA.update(temperature=0.9, top_k=0, top_p=1.0)

    ref_eng = paged_engine(net, f"{name}_ref", max_slots=2,
                           num_blocks=3, prefix_cache_blocks=0)
    try:
        ref = ref_eng.generate(pA, timeout=120, **kwA)
    finally:
        ref_eng.close()

    flight.clear()
    eng = paged_engine(net, name, max_slots=2, num_blocks=3,
                       prefix_cache_blocks=0)
    try:
        sA = eng.submit(pA, priority="batch", tenant="bulk", **kwA)
        it = iter(sA)
        head = [next(it) for _ in range(3)]   # A is mid-decode
        outB = eng.submit(pB, max_new_tokens=4,
                          priority="interactive",
                          tenant="live").result(timeout=120)
        tail = list(it)
        outA = np.asarray(head + tail, np.int32)
        assert len(outB) == 4
        return ref, outA, eng
    finally:
        eng.close()


@pytest.mark.slow    # tier-1 runtime budget: full e2e, run via --runslow
def test_preempt_resume_bit_exact_greedy(net):
    ref, outA, eng = preempt_scenario(net, "tsp_pre_g", do_sample=False)
    c = flight.counts()
    assert c.get("serve.preempt", 0) == 1
    assert c.get("serve.resume", 0) == 1
    assert np.array_equal(ref, outA)      # one seamless stream
    assert val("tsp_pre_g.request.preempted") == 1
    assert val("tsp_pre_g.request.resumed") == 1
    assert val("tsp_pre_g.tenant.bulk.preempted") == 1
    assert eng.pool.available == eng.pool.num_blocks   # drained free


@pytest.mark.slow    # tier-1 runtime budget: full e2e, run via --runslow
def test_preempt_resume_bit_exact_sampled(net):
    ref, outA, _eng = preempt_scenario(net, "tsp_pre_s", do_sample=True)
    c = flight.counts()
    assert c.get("serve.preempt", 0) == 1
    assert c.get("serve.resume", 0) == 1
    assert np.array_equal(ref, outA)


@pytest.mark.slow    # tier-1 runtime budget: full e2e, run via --runslow
def test_preempt_flight_event_fields(net):
    preempt_scenario(net, "tsp_pre_f", do_sample=False)
    evs = [f for _t, cat, ev, f in flight.events()
           if cat == "serve" and ev == "preempt"]
    assert len(evs) == 1
    (f,) = evs
    assert f["tenant"] == "bulk" and f["priority"] == "batch"
    assert f["blocks"] >= 1 and f["position"] >= 8
    assert f["engine"] == "tsp_pre_f"


def test_parked_deadline_sheds_typed(net):
    """A parked request whose deadline expires while swapped out must
    shed ``deadline_preempted`` — and release its host state — instead
    of resuming a stream nobody waits for."""
    pA = np.arange(1, 9, dtype=np.int32)
    pB = np.arange(1, 41, dtype=np.int32)
    flight.clear()
    eng = paged_engine(net, "tsp_dead", max_slots=2, num_blocks=3,
                       prefix_cache_blocks=0)
    try:
        sA = eng.submit(pA, max_new_tokens=40, priority="batch",
                        deadline_ms=60_000.0)
        it = iter(sA)
        for _ in range(3):
            next(it)
        sB = eng.submit(pB, max_new_tokens=8,
                        priority="interactive")
        # expire A's deadline deterministically: it is mid-slot now,
        # gets preempted by B's prefill, and the parked sweep must
        # shed it typed instead of resuming
        sA._req.deadline = time.monotonic() - 1.0
        sB.result(timeout=120)
        with pytest.raises(serving.DeadlineExceeded) as ei:
            sA.result(timeout=120)
        assert ei.value.reason == "deadline_preempted"
        c = flight.counts()
        assert c.get("serve.preempt", 0) == 1
        assert c.get("serve.resume", 0) == 0
        assert c.get("admission.deadline_preempted", 0) == 1
        assert val("tsp_dead.request.shed_deadline_preempted") == 1
    finally:
        eng.close()
    assert eng.pool.available == eng.pool.num_blocks


def test_no_preempt_within_same_class(net):
    """Pool pressure from an equal-priority request sheds the incoming
    request typed (kv_blocks) — preemption never bumps a peer."""
    pA = np.arange(1, 9, dtype=np.int32)
    pB = np.arange(1, 41, dtype=np.int32)
    flight.clear()
    eng = paged_engine(net, "tsp_peer", max_slots=2, num_blocks=3,
                       prefix_cache_blocks=0)
    try:
        sA = eng.submit(pA, max_new_tokens=30, priority="batch")
        it = iter(sA)
        for _ in range(3):
            next(it)
        with pytest.raises(serving.RequestRejected) as ei:
            eng.submit(pB, max_new_tokens=4,
                       priority="batch").result(timeout=120)
        assert ei.value.reason == "kv_blocks"
        assert flight.counts().get("serve.preempt", 0) == 0
        list(it)                          # A runs to completion
    finally:
        eng.close()


# -- engine-level quota + HTTP surface ---------------------------------

def test_engine_tenant_quota_and_hot_swap(net):
    eng = paged_engine(net, "tsp_equota", num_blocks=8,
                       tenant_quotas={"free": {"rate": 0.0,
                                               "burst": 12.0}})
    try:
        p = np.arange(1, 6, dtype=np.int32)
        eng.generate(p, max_new_tokens=4, tenant="free", timeout=60)
        with pytest.raises(serving.RequestRejected) as ei:
            eng.submit(p, max_new_tokens=4, tenant="free")
        assert ei.value.reason == "tenant_quota"
        # operator lifts the tenant's limit without a restart (empty
        # table, no "*" default -> unlimited)
        eng.set_quotas({})
        eng.generate(p, max_new_tokens=4, tenant="free", timeout=60)
    finally:
        eng.close()


def test_http_tenant_priority_and_retry_after(net):
    """X-Tenant/X-Priority ride the HTTP layer into admission; a quota
    429 answers Retry-After within [1, 30] and reason=tenant_quota."""
    import http.client
    eng = paged_engine(net, "tsp_http", num_blocks=8,
                       tenant_quotas={"free": {"rate": 0.0,
                                               "burst": 10.0}})
    srv = serving.ServingServer(eng).start()
    try:
        def post(tenant, priority):
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=30)
            body = json.dumps({"prompt_ids": [1, 2, 3],
                               "max_new_tokens": 3})
            conn.request("POST", "/v1/generate", body=body,
                         headers={"Content-Type": "application/json",
                                  "X-Tenant": tenant,
                                  "X-Priority": priority})
            r = conn.getresponse()
            data = json.loads(r.read().decode())
            ra = r.getheader("Retry-After")
            conn.close()
            return r.status, data, ra

        status, data, _ra = post("free", "interactive")
        assert status == 200 and len(data["tokens"]) == 3
        status, data, ra = post("free", "interactive")
        assert status == 429 and data["reason"] == "tenant_quota"
        assert ra is not None and 1 <= int(ra) <= 30
        assert val("tsp_http.tenant.free.admitted") == 1
        # typo'd priority class answers 400, not silent batch
        status, data, _ra = post("free", "vip")
        assert status == 400
    finally:
        srv.stop()
        eng.close()
