"""Multi-process training launcher (``python -m paddle_tpu.distributed.launch``).

Reference parity: ``python/paddle/distributed/fleet/launch.py:451`` (entry),
``:276`` launch_collective — spawn one trainer process per device with the
PADDLE_* env contract, stream logs, kill the pod on any failure, and
relaunch on the elastic exit code (``fleet/elastic/manager.py:26``).

TPU-first: one process per *host* (a pod slice host drives all its local
chips through one PJRT client), identified to ``jax.distributed`` via
coordinator address + process id; ``--nproc`` > 1 on a single machine is
the CPU-simulation path, where each process gets an
``xla_force_host_platform_device_count`` virtual mesh for test parity
(reference TestDistBase's localhost multi-process cluster).  A chip
belongs to one process: ``--nproc`` > 1 whose children are not pinned to
the CPU (``--devices_per_proc`` or ``JAX_PLATFORMS=cpu``) is refused —
each child would open every local chip.  The launcher itself never
touches JAX, so it holds no chip its children need.

Supervisor mode (``--supervise``, TorchElastic-style): the launcher
heartbeats workers through the elastic ``Store`` (workers put step
payloads under ``/paddle/supervise/<job>/g<generation>/<rank>`` — hapi
``Model.fit`` does this automatically when ``PADDLE_SUPERVISE_STORE``
is set), detects both crashes (nonzero exit) and hung steps (no
heartbeat advance within ``FLAGS_watchdog_timeout``), kills the gang,
bumps ``PADDLE_RESTART_GENERATION``, and relaunches up to
``--max_restarts`` times.  Workers are expected to resume from the
newest intact checkpoint (``AsyncCheckpointer.restore``), so a restart
costs re-execution since the last commit, not the whole run.

Elastic supervise (``--supervise --np MIN:MAX``): the degraded-but-
running mode.  When a failure looks like a *lost host* — death by
signal, a watchdog stall, or (under ``--evict_stragglers``) a rank
whose per-step wall time exceeds ``FLAGS_straggler_factor`` x the gang
median for ``FLAGS_straggler_patience`` consecutive heartbeat samples
— the supervisor runs a store-based rendezvous round (generation-
prefixed TTL lease keys, so stale ranks from prior generations can't
join), drops the lost host's slot onto a rendezvous denylist, and
relaunches with whatever world size survives within ``[MIN, MAX]``.
Shrink-relaunches do NOT consume the ``--max_restarts`` budget:
degradation is not failure.  A plain software crash (nonzero exit
code) keeps the full world and spends the budget as before.  Workers
learn the new world through the standard ``PADDLE_TRAINERS_NUM`` /
``PADDLE_TRAINER_ID`` env contract; cross-world checkpoint resume is
``distributed.checkpoint``'s manifest-v2 reshard path + ``Model.fit``'s
sample-exact replay-offset recompute.
"""
from __future__ import annotations

import argparse
import json
import os
import shlex
import signal
import statistics
import subprocess
import sys
import tempfile
import time
from collections import deque

# single source of truth for the relaunch protocol + np parsing
from .fleet.elastic.manager import ELASTIC_EXIT_CODE, _parse_np  # noqa: E402

SUPERVISE_PREFIX = "/paddle/supervise/"
RDZV_PREFIX = "/paddle/rendezvous/"
SERVING_PREFIX = "/paddle/serving/"


def serving_key(job: str, generation, replica) -> str:
    """The generation-prefixed serving-registry lease key.  The same
    fencing pattern as :func:`heartbeat_key`: an engine replica claims
    ``/paddle/serving/<job>/g<generation>/<replica>`` as a TTL lease
    (``serving/fleet.py ReplicaRegistry``) and republishes its health/
    occupancy payload on a heartbeat cadence; a stale replica from a
    prior generation holds a lease under a different prefix, so a
    router scoped to the live generation can never dispatch to it."""
    return f"{SERVING_PREFIX}{job}/g{generation}/{replica}"


def heartbeat_key(job: str, generation, rank) -> str:
    """The generation-prefixed supervise heartbeat key.  Scoping the key
    to the restart generation means a slow-dying worker from generation
    N keeps writing under ``g<N>/`` — invisible to the generation-N+1
    watchdog, which lists only its own prefix (and the supervisor also
    deletes prior-generation keys at each relaunch)."""
    return f"{SUPERVISE_PREFIX}{job}/g{generation}/{rank}"


def _parse_beat(value):
    """Decode one heartbeat payload: JSON ``{"step": s, "dt": secs}``
    (v2, ``dt`` = mean per-step wall time since the previous beat) or a
    bare step token (v1 / hand-rolled scripts).  Returns
    ``(step_token, dt_or_None)``."""
    if isinstance(value, str) and value[:1] == "{":
        try:
            d = json.loads(value)
            if isinstance(d, dict) and "step" in d:
                dt = d.get("dt")
                return d["step"], (float(dt) if dt is not None else None)
        except (ValueError, TypeError):
            pass
    return value, None


class StragglerTracker:
    """Rolling per-rank step-time medians from heartbeat payloads.

    Each fresh sample (a beat whose step advanced, carrying a ``dt``)
    updates that rank's rolling median (window of 8).  The gang median
    is the median of the *other* ranks' medians — excluding the
    candidate keeps a 2-rank gang meaningful (with it included, a
    2-rank median can never exceed 2x itself).  A rank whose median
    exceeds ``factor`` x the gang median accrues one strike per fresh
    sample, resets on a healthy sample, and is flagged once per
    generation when strikes reach ``patience`` — counted as
    ``launch.straggler`` and recorded for the supervise report.
    Detection is pure bookkeeping; the eviction policy stays in the
    supervisor loop."""

    WINDOW = 8
    MIN_SAMPLES = 2

    def __init__(self, factor: float, patience: int, generation: int = 0):
        self.factor = float(factor)
        self.patience = max(1, int(patience))
        self.generation = int(generation)
        self.reports = []
        self._times = {}
        self._strikes = {}
        self._samples = {}
        self._flagged = set()

    def observe(self, rank: str, dt: float):
        """One fresh per-step wall-time sample for ``rank``.  Returns
        the straggler report dict when this exact sample crosses the
        patience threshold, else None."""
        q = self._times.setdefault(rank, deque(maxlen=self.WINDOW))
        q.append(float(dt))
        self._samples[rank] = self._samples.get(rank, 0) + 1
        if rank in self._flagged or len(q) < self.MIN_SAMPLES:
            return None
        meds = {r: statistics.median(t) for r, t in self._times.items()
                if len(t) >= self.MIN_SAMPLES}
        others = [m for r, m in meds.items() if r != rank]
        if not others:
            return None
        gang = statistics.median(others)
        mine = meds[rank]
        if not (gang > 0 and mine > self.factor * gang):
            self._strikes[rank] = 0
            return None
        self._strikes[rank] = self._strikes.get(rank, 0) + 1
        if self._strikes[rank] < self.patience:
            return None
        self._flagged.add(rank)
        report = {"generation": self.generation, "rank": str(rank),
                  "median_s": round(mine, 6),
                  "gang_median_s": round(gang, 6),
                  "strikes": self._strikes[rank],
                  "samples": self._samples[rank]}
        self.reports.append(report)
        from ..profiler import metrics as _metrics
        _metrics.counter(
            "launch.straggler",
            "ranks whose rolling per-step median exceeded "
            "FLAGS_straggler_factor x the gang median for "
            "FLAGS_straggler_patience consecutive samples").inc()
        print(f"launch: rank {rank} is a straggler — median step "
              f"{mine:.3f}s vs gang {gang:.3f}s "
              f"(factor {self.factor}, {report['strikes']} strikes over "
              f"{report['samples']} samples)", file=sys.stderr)
        return report


def _parse_args(argv=None):
    p = argparse.ArgumentParser(
        prog="paddle_tpu.distributed.launch",
        description="launch a multi-process training job")
    p.add_argument("--nproc", "--nproc_per_node", type=int, default=1,
                   dest="nproc", help="processes to spawn on this host")
    p.add_argument("--ips", type=str, default="127.0.0.1",
                   help="comma-separated host list (multi-host)")
    p.add_argument("--host_rank", type=int, default=0,
                   help="index of this host in --ips")
    p.add_argument("--master_port", type=int, default=36007)
    p.add_argument("--log_dir", type=str, default=None,
                   help="write per-rank logs under this dir")
    p.add_argument("--devices_per_proc", type=int, default=0,
                   help="if >0, give each proc an N-device virtual CPU mesh")
    p.add_argument("--elastic", action="store_true",
                   help=f"relaunch the pod when a proc exits with code "
                        f"{ELASTIC_EXIT_CODE}")
    p.add_argument("--np", type=str, default=None,
                   help="MIN:MAX elastic world bounds.  With --elastic: "
                        "each (re)launch sizes the pod to the live "
                        "member count in the elastic store "
                        "(PADDLE_ELASTIC_STORE_ROOT), like the "
                        "reference's etcd-driven scale in/out.  With "
                        "--supervise: enables elastic supervise — a "
                        "lost host (signal death / watchdog stall / "
                        "evicted straggler) shrinks the relaunched "
                        "world within these bounds instead of burning "
                        "a restart on a gang that can't re-form")
    p.add_argument("--max_restarts", type=int, default=3)
    p.add_argument("--supervise", action="store_true",
                   help="babysit the gang: relaunch on ANY worker crash "
                        "or hung-step stall (watchdog over store "
                        "heartbeats), bumping PADDLE_RESTART_GENERATION "
                        "each attempt, up to --max_restarts; add "
                        "--np MIN:MAX to relaunch elastically at the "
                        "surviving world size (shrinks don't consume "
                        "the restart budget)")
    p.add_argument("--evict_stragglers", action="store_true",
                   help="with --supervise --np MIN:MAX: when a rank's "
                        "rolling median step time exceeds "
                        "FLAGS_straggler_factor x the gang median for "
                        "FLAGS_straggler_patience consecutive "
                        "heartbeat samples, treat it as a stall — kill "
                        "the gang and re-form WITHOUT that host via a "
                        "rendezvous denylist entry (without this flag "
                        "stragglers are only reported: launch.straggler "
                        "metric + supervise report JSON)")
    p.add_argument("--watchdog_timeout", type=float, default=None,
                   help="seconds without heartbeat-step progress before "
                        "a worker counts as hung (default: "
                        "FLAGS_watchdog_timeout); 0 disables stall "
                        "detection")
    p.add_argument("training_script", type=str)
    p.add_argument("training_script_args", nargs=argparse.REMAINDER)
    args = p.parse_args(argv)
    if args.np:
        try:
            lo, hi = _parse_np(args.np)
        except ValueError:
            p.error(f"bad --np {args.np!r}: expected N or MIN:MAX")
        if lo < 1 or hi < lo:
            p.error(f"bad --np {args.np!r}: need 1 <= MIN <= MAX")
    if args.supervise and args.elastic and not args.np:
        # the historical exclusion, lifted into the unified mode: the
        # supervisor CAN resize, but only with explicit world bounds
        p.error("--supervise --elastic needs --np MIN:MAX: elastic "
                "supervise relaunches at the surviving world size "
                "within those bounds")
    most = max(args.nproc, _parse_np(args.np)[1] if args.np else 0)
    if most > 1 and args.devices_per_proc <= 0 \
            and os.environ.get("JAX_PLATFORMS") != "cpu":
        p.error(f"{most} processes on one host would each open every "
                "local accelerator chip, and a chip belongs to one "
                "process: run one process per host (--nproc 1 drives "
                "all local chips), or pin the children to the CPU "
                "simulation with --devices_per_proc N or "
                "JAX_PLATFORMS=cpu")
    if args.evict_stragglers and not (args.supervise and args.np):
        p.error("--evict_stragglers requires --supervise --np MIN:MAX "
                "(eviction re-forms the gang one host smaller, which "
                "needs elastic world bounds)")
    return args


def get_cluster_env(rank, world_size, endpoints, coordinator):
    """The PADDLE_* env contract (reference distributed/utils.py Cluster/Pod
    + parallel.py:69 ParallelEnv consumption)."""
    return {
        "PADDLE_TRAINER_ID": str(rank),
        "PADDLE_TRAINERS_NUM": str(world_size),
        "PADDLE_CURRENT_ENDPOINT": endpoints[rank],
        "PADDLE_TRAINER_ENDPOINTS": ",".join(endpoints),
        "PADDLE_MASTER": coordinator,
    }


class PodLauncher:
    """Spawn + babysit one host's trainer processes
    (reference fleet/elastic/manager.py:37 LauncherInterface)."""

    def __init__(self, args, argv_tail, extra_env=None):
        self.args = args
        self.argv_tail = argv_tail
        self.extra_env = dict(extra_env or {})
        self.procs = []
        self.log_files = []

    def launch(self):
        a = self.args
        hosts = [h.strip() for h in a.ips.split(",") if h.strip()]
        world = len(hosts) * a.nproc
        endpoints = [f"{h}:{a.master_port + i}"
                     for h in hosts for i in range(a.nproc)]
        coordinator = f"{hosts[0]}:{a.master_port - 1}"
        if a.log_dir:
            os.makedirs(a.log_dir, exist_ok=True)
        self.procs, self.log_files = [], []
        for local in range(a.nproc):
            rank = a.host_rank * a.nproc + local
            env = dict(os.environ)
            env.update(get_cluster_env(rank, world, endpoints, coordinator))
            env.update(self.extra_env)
            # children must import the same framework as this parent even
            # when it is run from a source tree rather than installed
            pkg_root = os.path.dirname(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))))
            env["PYTHONPATH"] = os.pathsep.join(
                [pkg_root] + ([env["PYTHONPATH"]]
                              if env.get("PYTHONPATH") else []))
            if a.devices_per_proc > 0:
                env["JAX_PLATFORMS"] = "cpu"
                env["XLA_FLAGS"] = (
                    env.get("XLA_FLAGS", "") +
                    f" --xla_force_host_platform_device_count="
                    f"{a.devices_per_proc}").strip()
            cmd = [sys.executable, a.training_script] + self.argv_tail
            if a.log_dir:
                f = open(os.path.join(a.log_dir, f"workerlog.{rank}"), "w")
                self.log_files.append(f)
                proc = subprocess.Popen(cmd, env=env, stdout=f, stderr=f)
            else:
                proc = subprocess.Popen(cmd, env=env)
            self.procs.append(proc)
        return self.procs

    def wait(self):
        """Block until all procs exit; on any failure kill the pod.
        Returns the pod's exit code (first nonzero, else 0)."""
        pending = {p.pid: p for p in self.procs}
        code = 0
        while pending:
            for pid, p in list(pending.items()):
                rc = p.poll()
                if rc is None:
                    continue
                del pending[pid]
                if rc != 0:
                    code = code or rc
                    self.stop()
                    pending.clear()
                    break
            time.sleep(0.1)
        self._close_logs()
        return code

    def stop(self):
        for p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.time() + 10
        for p in self.procs:
            timeout = max(0.1, deadline - time.time())
            try:
                p.wait(timeout=timeout)
            except subprocess.TimeoutExpired:
                p.kill()

    def dump_stacks(self, settle: float = 0.5):
        """Ask every live worker for a thread dump (SIGUSR1 -> the
        handler installed by ``Model.fit`` under supervision /
        ``concurrency.install_signal_dump``) before the gang is
        killed, so a watchdog-stalled worker's log ends with all
        thread stacks + held sanitizer locks instead of going dark."""
        signalled = False
        for p in self.procs:
            if p.poll() is None:
                try:
                    p.send_signal(signal.SIGUSR1)
                    signalled = True
                except (OSError, AttributeError,
                        ValueError):   # gone / no SIGUSR1 (windows)
                    pass
        if signalled:
            time.sleep(settle)   # let handlers flush before SIGTERM

    def _close_logs(self):
        for f in self.log_files:
            f.close()
        self.log_files = []

    def supervise(self, store, job: str, watchdog: float,
                  poll: float = 0.2, *, generation: int = 0,
                  straggler=None, evict_stragglers: bool = False):
        """Babysit the gang.  Returns ``(kind, detail, victim_rank)``:

        - ``("done", 0, None)`` — every worker exited cleanly;
        - ``("crash", rc, rank)`` — first nonzero exit (``rc < 0`` is
          death by signal, which elastic supervise reads as host loss);
        - ``("stall", key, rank)`` — a heartbeating worker stopped
          advancing its step for ``watchdog`` seconds;
        - ``("straggler", key, rank)`` — only with
          ``evict_stragglers``: the ``straggler`` tracker flagged the
          rank, so the gang is killed for an eviction re-form.

        Crash/stall/eviction kills the whole gang (partial pods can't
        make progress — reference launch.py terminate_local_procs).
        Only heartbeat keys under THIS generation's prefix are read, so
        a slow-dying worker from a prior generation can't feed this
        watchdog.

        Stall detection is opt-in by construction: a worker that never
        writes a heartbeat (a script not using Model.fit) is only
        covered by crash detection — the watchdog can't distinguish
        "doesn't heartbeat" from "hung before the first beat", and
        killing every non-heartbeating script would be worse."""
        last = {}  # heartbeat key -> (step_token, t_last_changed)
        beat_t = 0.0
        a = self.args
        try:
            while True:
                rcs = [p.poll() for p in self.procs]
                bad = next(((rc, i) for i, rc in enumerate(rcs)
                            if rc not in (None, 0)), None)
                if bad is not None:
                    # signal the survivors before killing the gang so
                    # each one's log ends with its thread stacks AND
                    # flight-recorder tail (the dead rank can't dump —
                    # its gangmates' history is the evidence left)
                    self.dump_stacks()
                    self.stop()
                    return "crash", bad[0], a.host_rank * a.nproc + bad[1]
                if all(rc == 0 for rc in rcs):
                    return "done", 0, None
                # a cleanly-exited worker's heartbeat stops advancing by
                # definition — it must never trip the stall watchdog
                done_ranks = {str(a.host_rank * a.nproc + local)
                              for local, rc in enumerate(rcs) if rc == 0}
                now = time.monotonic()
                if store is not None and now - beat_t >= poll and \
                        (watchdog or straggler is not None):
                    beat_t = now
                    try:
                        beats = store.list_prefix(
                            f"{SUPERVISE_PREFIX}{job}/g{generation}/")
                    except Exception:
                        beats = None   # store blip: skip this round
                    if beats is not None:
                        for k, v in beats.items():
                            step, dt = _parse_beat(v)
                            prev = last.get(k)
                            if prev is not None and prev[0] == step:
                                continue
                            last[k] = (step, now)
                            rank = k.rsplit("/", 1)[-1]
                            if straggler is None or dt is None or \
                                    rank in done_ranks:
                                continue
                            rep = straggler.observe(rank, dt)
                            if rep is not None and evict_stragglers:
                                print(f"launch: evicting straggler "
                                      f"rank {rank} — killing the gang "
                                      f"to re-form without it",
                                      file=sys.stderr)
                                self.dump_stacks()
                                self.stop()
                                return "straggler", k, rank
                        if watchdog:
                            for k, (v, t) in last.items():
                                rank = k.rsplit("/", 1)[-1]
                                if rank in done_ranks:
                                    continue
                                if now - t > watchdog:
                                    print(f"launch: worker heartbeat "
                                          f"{k} stuck at {v!r} for "
                                          f"{now - t:.1f}s (watchdog "
                                          f"{watchdog}s) — killing the "
                                          f"gang", file=sys.stderr)
                                    self.dump_stacks()
                                    self.stop()
                                    return "stall", k, rank
                time.sleep(poll)
        finally:
            self._close_logs()


def launch(argv=None):
    args = _parse_args(argv)
    tail = list(args.training_script_args)
    if tail and tail[0] == "--":
        tail = tail[1:]
    restarts = 0
    pod_ref = {}

    def _sig(_s, _f):
        # reads the live pod through the holder so elastic relaunches are
        # covered; installed before the first spawn so no orphan window
        if pod_ref.get("pod") is not None:
            pod_ref["pod"].stop()
        sys.exit(1)

    signal.signal(signal.SIGTERM, _sig)

    def _elastic_world():
        """Size the pod to the live membership (reference manager.py
        etcd host set -> np within [min, max])."""
        if not (args.elastic and args.np and
                os.environ.get("PADDLE_ELASTIC_STORE_ROOT")):
            return
        from .fleet.elastic.manager import (ElasticManager, _parse_np,
                                            store_from_spec)
        lo, hi = _parse_np(args.np)
        store = store_from_spec(os.environ["PADDLE_ELASTIC_STORE_ROOT"])
        job = os.environ.get("PADDLE_ELASTIC_JOB_ID", "default")
        pfx = f"{ElasticManager.PREFIX}{job}/"
        deadline = time.time() + float(
            os.environ.get("PADDLE_ELASTIC_WAIT_S", "60"))
        live = None
        while True:
            try:
                live = len(store.list_prefix(pfx))
            except Exception as e:
                # store briefly unreachable mid-recovery: keep the
                # previous world size rather than dying
                print(f"launch: elastic store unreachable ({e!r})",
                      file=sys.stderr)
            if (live is not None and live >= lo) or                     time.time() > deadline:
                break
            time.sleep(0.5)
        if live is None:
            return
        args.nproc = max(lo, min(hi, live if live else args.nproc))
        print(f"launch: elastic world = {args.nproc} "
              f"(live members {live}, bounds {lo}:{hi})", file=sys.stderr)

    if args.supervise:
        return _supervised_loop(args, tail, pod_ref)

    while True:
        _elastic_world()
        pod = PodLauncher(args, tail)
        pod_ref["pod"] = pod
        pod.launch()
        code = pod.wait()
        if code == 0:
            return 0
        if args.elastic and code == ELASTIC_EXIT_CODE and \
                restarts < args.max_restarts:
            restarts += 1
            print(f"launch: elastic exit ({code}); relaunch "
                  f"{restarts}/{args.max_restarts}", file=sys.stderr)
            continue
        print(f"launch: pod failed with exit code {code} "
              f"(cmd: {shlex.join([args.training_script] + tail)})",
              file=sys.stderr)
        return code


def _rendezvous_round(store, job: str, generation: int, slots,
                      hi: int, ttl: float = 60.0):
    """One store-based rendezvous round forming ``generation``'s gang:
    read the denylist (``/paddle/rendezvous/<job>/deny/<slot>`` —
    written when a host is evicted), grant every surviving slot up to
    ``hi``, and claim a generation-prefixed TTL lease per granted slot
    (``.../g<gen>/<slot>``).  The generation prefix is the fencing
    token: a stale rank from a prior generation holds a lease under a
    different prefix (which its TTL also expires), so it can never
    count toward — or join — the new gang.  Store outages degrade to
    the supervisor's local membership view: a rendezvous round never
    blocks a relaunch.  Counted as ``launch.rendezvous_rounds``."""
    from ..profiler import flight as _flight
    from ..profiler import metrics as _metrics
    _metrics.counter(
        "launch.rendezvous_rounds",
        "elastic-supervise rendezvous rounds (one per gang "
        "formation)").inc()
    if _flight.active:
        _flight.note("launch", "rendezvous", generation=generation,
                     slots=len(slots))
    deny = set()
    try:
        deny = {k.rsplit("/", 1)[-1] for k in
                store.list_prefix(f"{RDZV_PREFIX}{job}/deny/")}
    except Exception as e:
        print(f"launch: rendezvous denylist unreadable ({e!r}); "
              f"using the local membership view", file=sys.stderr)
    granted = [s for s in slots if s not in deny][:max(1, int(hi))]
    pfx = f"{RDZV_PREFIX}{job}/g{generation}/"
    for s in granted:
        try:
            store.put(f"{pfx}{s}", "lease", ttl=ttl)
        except Exception:
            pass   # lease is the observable record, not the decision
    return granted


def _deny_slot(store, job: str, slot: str):
    """Record an evicted host slot on the rendezvous denylist so no
    later round re-admits it."""
    try:
        store.put(f"{RDZV_PREFIX}{job}/deny/{slot}", "denied")
    except Exception as e:
        print(f"launch: could not record denylist entry for {slot} "
              f"({e!r}); supervisor-local eviction still holds",
              file=sys.stderr)


def _purge_stale_generations(store, job: str, generation: int):
    """Delete heartbeat, fleet-metrics AND serving-registry keys from
    generations before ``generation``.  Ignore-by-prefix in ``supervise`` is the
    correctness mechanism (a slow-dying worker can rewrite its old key
    after this purge); the delete is hygiene so the store doesn't
    accrete one key set per restart."""
    from .fleet_metrics import METRICS_PREFIX
    for root in (SUPERVISE_PREFIX, METRICS_PREFIX, SERVING_PREFIX):
        pfx = f"{root}{job}/"
        keep = f"{pfx}g{generation}/"
        try:
            for k in store.list_prefix(pfx):
                if not k.startswith(keep):
                    store.delete(k)
        except Exception:
            pass


def _supervised_loop(args, tail, pod_ref):
    """Supervisor mode: spawn, babysit, and relaunch the gang until it
    completes or the restart budget is spent.  Each attempt runs with
    PADDLE_RESTART_GENERATION set so workers know they are a resume.

    With ``--np MIN:MAX`` (elastic supervise) a lost host — death by
    signal, watchdog stall, or evicted straggler — shrinks the next
    generation's world within the bounds instead of consuming the
    restart budget; a plain software crash (nonzero exit code) keeps
    the world and spends the budget, as before."""
    from .fleet.elastic.manager import KVServer, store_from_spec
    from ..profiler import metrics as _metrics
    from ..utils import flags as _flags

    watchdog = args.watchdog_timeout
    if watchdog is None:
        watchdog = _flags.get_flag("FLAGS_watchdog_timeout")
    elastic = bool(args.np)
    lo, hi = _parse_np(args.np) if elastic else (args.nproc, args.nproc)
    if elastic:
        args.nproc = max(lo, min(hi, args.nproc))
    job = os.environ.get("PADDLE_SUPERVISE_JOB",
                         f"job-{os.getpid()}")
    spec = os.environ.get("PADDLE_ELASTIC_STORE_ROOT")
    server = None
    if not spec:
        # no store configured: run the KV endpoint ourselves (the
        # coordinator-host etcd analog) so heartbeats have a home
        server = KVServer().start()
        spec = f"tcp://{server.endpoint}"
    store = store_from_spec(spec)
    # flight-recorder dump directory: every worker's SIGUSR1/crash
    # dumps (and the supervisor's own) land here, then fold into the
    # supervise report — the post-mortem starts pre-assembled
    flight_dir = os.environ.get("PADDLE_FLIGHT_DIR")
    if not flight_dir:
        flight_dir = args.log_dir or tempfile.mkdtemp(
            prefix="paddle_flight_")
        os.environ["PADDLE_FLIGHT_DIR"] = flight_dir
    os.makedirs(flight_dir, exist_ok=True)
    # a reused --log_dir may hold a PREVIOUS run's flight dumps; only
    # dumps written after this instant belong in this run's report
    flight_t0 = time.time()
    # aggregated fleet /metrics endpoint (opt-in by port): every
    # rank's registry snapshot, rank-labeled + min/max/sum rollups
    gen_ref = {"g": 0}
    metrics_server = None
    mport = os.environ.get("PADDLE_FLEET_METRICS_PORT")
    if mport is not None:
        from .fleet_metrics import FleetMetricsServer
        try:
            metrics_server = FleetMetricsServer(
                spec, job, lambda: gen_ref["g"],
                port=int(mport)).start()
            print(f"launch: fleet metrics at http://"
                  f"{metrics_server.host}:{metrics_server.port}"
                  f"/metrics", file=sys.stderr)
        except Exception as e:
            print(f"launch: fleet metrics server failed ({e!r}); "
                  f"continuing without aggregation", file=sys.stderr)
    interval = os.environ.get("PADDLE_HEARTBEAT_INTERVAL", "1.0")
    factor = _flags.get_flag("FLAGS_straggler_factor")
    patience = _flags.get_flag("FLAGS_straggler_patience")
    restarts = 0        # budget-consuming (same-world) restarts
    shrinks = 0         # world-shrinking relaunches: NOT failures
    generation = 0
    rdzv_rounds = 0
    downtime_s = 0.0    # failure-detected -> next gang up (restart
    down_t0 = None      # badput the workers can't see themselves)
    # stable host-slot labels: rank numbering is contiguous per
    # generation, but eviction identity must survive renumbering.
    # Host-qualified so a multi-host job's shared deny prefix can't
    # make host A's eviction of its slot 1 denylist every other
    # host's slot 1 as well.
    slots = [f"h{args.host_rank}-s{i}" for i in range(args.nproc)]
    world_history = []
    stragglers = []
    counter = _metrics.counter(
        "launch.restarts", "supervised gang relaunches (crash, "
        "watchdog stall, straggler eviction, or elastic shrink)")
    outcome = {"kind": "done", "code": 0}
    try:
        while True:
            gen_ref["g"] = generation
            if elastic:
                slots = _rendezvous_round(store, job, generation, slots,
                                          hi)
                rdzv_rounds += 1
                if len(slots) < lo:
                    print(f"launch: rendezvous formed only "
                          f"{len(slots)} member(s), below the --np "
                          f"floor {lo}; giving up", file=sys.stderr)
                    outcome = {"kind": "underworld", "code": 1}
                    return 1
            args.nproc = len(slots) if elastic else args.nproc
            world_history.append(args.nproc)
            tracker = None
            if factor and factor > 0:
                tracker = StragglerTracker(factor, patience,
                                           generation=generation)
            pod = PodLauncher(args, tail, extra_env={
                "PADDLE_SUPERVISE_STORE": spec,
                "PADDLE_SUPERVISE_JOB": job,
                "PADDLE_HEARTBEAT_INTERVAL": str(interval),
                "PADDLE_RESTART_GENERATION": str(generation),
            })
            pod_ref["pod"] = pod
            pod.launch()
            if down_t0 is not None:
                downtime_s += time.time() - down_t0
                down_t0 = None
            kind, detail, victim = pod.supervise(
                store, job, watchdog, generation=generation,
                straggler=tracker,
                evict_stragglers=args.evict_stragglers)
            if tracker is not None:
                stragglers.extend(tracker.reports)
            if kind == "done":
                outcome = {"kind": "done", "code": 0}
                return 0
            down_t0 = time.time()
            # host-loss attribution: a signal death, a stall, or an
            # evicted straggler means the HOST is gone/useless; a plain
            # nonzero exit is a software crash on a healthy host
            lost_host = kind in ("stall", "straggler") or \
                (kind == "crash" and isinstance(detail, int) and
                 detail < 0)
            # map the victim's GLOBAL rank onto a slot THIS supervisor
            # owns (rank = host_rank * nproc + local slot index); an
            # unmappable victim (a remote host's rank in a multi-host
            # pod, where only that host's supervisor can drop the
            # slot) must fall through to the budgeted restart path —
            # shrinking by a slot we don't own would loop forever
            # without ever degrading the world
            victim_slot = None
            if elastic and lost_host and victim is not None:
                try:
                    vi = int(victim) - args.host_rank * args.nproc
                except (TypeError, ValueError):
                    vi = -1
                if 0 <= vi < len(slots):
                    victim_slot = slots[vi]
            if victim_slot is not None and len(slots) - 1 >= lo:
                _deny_slot(store, job, victim_slot)
                slots = [s for s in slots if s != victim_slot]
                shrinks += 1
                generation += 1
                counter.inc()
                _purge_stale_generations(store, job, generation)
                print(f"launch: worker {kind} ({detail}) read as host "
                      f"loss — degrading to world {len(slots)} "
                      f"(bounds {lo}:{hi}, slot {victim_slot} "
                      f"denylisted; shrink-restarts don't consume "
                      f"--max_restarts)", file=sys.stderr)
                continue
            if restarts < args.max_restarts:
                restarts += 1
                generation += 1
                counter.inc()
                _purge_stale_generations(store, job, generation)
                print(f"launch: worker {kind} ({detail}); supervised "
                      f"relaunch {restarts}/{args.max_restarts} "
                      f"(workers resume from the newest intact "
                      f"checkpoint)", file=sys.stderr)
                continue
            code = detail if kind == "crash" else 1
            print(f"launch: {kind} ({detail}) with restart budget "
                  f"spent ({args.max_restarts}); giving up",
                  file=sys.stderr)
            outcome = {"kind": kind, "code": code}
            return code if code else 1
    finally:
        # the supervisor's own flight ring (rendezvous rounds,
        # per-generation formation history) joins the workers' dumps
        from ..profiler import flight as _flight
        _flight.dump(os.path.join(flight_dir, "flight.supervisor.json"),
                     reason="supervise-exit")
        report = os.environ.get("PADDLE_SUPERVISE_REPORT")
        if report:
            with open(report, "w") as f:
                json.dump({"restarts": restarts,
                           "restarts_metric": counter.value,
                           "shrinks": shrinks,
                           "world": world_history[-1] if world_history
                           else args.nproc,
                           "world_history": world_history,
                           "generation": generation,
                           "rendezvous_rounds": rdzv_rounds,
                           "stragglers": stragglers,
                           "flight_dir": flight_dir,
                           "flight_dumps": _collect_flight_dumps(
                               flight_dir, min_mtime=flight_t0),
                           "downtime_s": round(downtime_s, 3),
                           "goodput": _collect_goodput(
                               flight_dir, min_mtime=flight_t0),
                           **outcome}, f)
        if metrics_server is not None:
            metrics_server.stop()
        if server is not None:
            server.stop()


def _collect_flight_dumps(flight_dir: str, tail: int = 10,
                          min_mtime: float = 0.0):
    """Fold this run's flight dumps under ``flight_dir`` into the
    supervise report: per dump, the event counts and the last ``tail``
    events — enough for a first read of *what the gang was doing*
    without opening each file.  ``min_mtime`` fences out stale dumps a
    previous run left in a reused log directory."""
    out = {}
    try:
        names = sorted(os.listdir(flight_dir))
    except OSError:
        return out
    for name in names:
        if not (name.startswith("flight.") and name.endswith(".json")):
            continue
        path = os.path.join(flight_dir, name)
        try:
            if os.path.getmtime(path) < min_mtime:
                continue
            with open(path) as f:
                doc = json.load(f)
        except (OSError, ValueError):
            continue
        evs = doc.get("events") or []
        out[name] = {"reason": doc.get("reason"),
                     "rank": doc.get("rank"),
                     "generation": doc.get("generation"),
                     "events": len(evs),
                     "counts": doc.get("counts") or {},
                     "tail": [f"{e.get('cat')}.{e.get('event')}"
                              for e in evs[-tail:]]}
    return out


def _collect_goodput(flight_dir: str, min_mtime: float = 0.0):
    """Fold the workers' ``goodput.r<rank>.g<gen>.json`` docs (written
    by ``profiler.memscope.GoodputMeter.finish``) into the supervise
    report, so one file answers "how much of the run's wall-clock was
    productive step time" across restarts.  Same mtime fence as the
    flight dumps."""
    out = {}
    try:
        names = sorted(os.listdir(flight_dir))
    except OSError:
        return out
    for name in names:
        if not (name.startswith("goodput.") and name.endswith(".json")):
            continue
        path = os.path.join(flight_dir, name)
        try:
            if os.path.getmtime(path) < min_mtime:
                continue
            with open(path) as f:
                out[name] = json.load(f)
        except (OSError, ValueError):
            continue
    return out


if __name__ == "__main__":
    sys.exit(launch())
