"""Content-addressed AOT executable artifact store.

jax's persistent compilation cache (``utils/compile_cache.py``) already
spares a relaunch the *XLA* compile, but every process still pays the
trace + lowering + cache probe inside ``jit``'s dispatch, and subsystems
that compile **ahead of time** (serving's bucketed ``ExecutableCache``,
``GenerationSession`` prefill/decode, ``Model.fit``'s train step, the
static Executor) each call ``lowered.compile()`` themselves.  This store
short-circuits that call: serialized compiled executables
(``jax.experimental.serialize_executable``) are persisted on disk keyed
by a **content fingerprint** of the lowered program —

    sha256(StableHLO text ‖ jax version ‖ jaxlib version ‖ backend
           platform ‖ device kind/count ‖ caller extra key)

— so the bucket signature, mesh/sharding, and program/step identity are
all captured by construction (they are *in* the lowered module), and a
jax or XLA upgrade can never serve a stale executable (the version is
in the key AND re-checked from the entry header on load).

Entry layout (``<root>/objects/<fp[:2]>/<fp>.bin``)::

    PTAOT1\\n
    {json header: payload sha256+size, jax/jaxlib/backend, label,
                  ids of the devices the executable runs on}\\n
    <pickled (serialized_executable, in_tree, out_tree)>

Every load re-hashes the payload against the header (the PR 3 manifest
pattern): truncated, bit-flipped, or version-mismatched entries **miss
cleanly** — counted, quarantine-deleted, recompiled — and never serve
wrong code; an intact entry that then fails to load raises.  ``<root>/index.json`` tracks per-entry size and
last-use for the LRU size-cap GC (``FLAGS_aot_store_max_mb``); the
blobs are self-verifying, so a lost or stale index only costs GC
bookkeeping, not correctness.

Metrics (PR 1 registry): ``aot_store.hit`` / ``miss`` / ``store`` /
``corrupt`` / ``evicted`` / ``bypass``.

The module-level store arms from ``FLAGS_compile_cache_dir`` (root =
``<dir>/artifacts``) at import and on every ``set_flags``, and from
nothing else: jax's persistent cache is placed independently
(``utils/compile_cache.py``) and does not arm the store.
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import time
from typing import Optional, Tuple

import jax

from . import concurrency as _conc
from . import flags as _flags

__all__ = ["ArtifactStore", "active", "configure", "aot_compile",
           "fingerprint_lowered", "stats"]

_MAGIC = b"PTAOT1\n"
_METRIC_PREFIX = "aot_store"


def _jax_cache_hits() -> int:
    """Compiles that JAX's own persistent cache served
    (utils/compile_cache.py), as the launch record counts them."""
    from ..profiler import metrics as _metrics
    return _metrics.counter("compile.cache_hit").value


def _m(name: str):
    from ..profiler import metrics as _metrics
    docs = {
        "hit": "AOT compiles served from the artifact store (no XLA "
               "compile paid)",
        "miss": "artifact-store lookups that fell through to a fresh "
                "lowered.compile()",
        "store": "freshly compiled executables persisted to the store",
        "corrupt": "entries rejected by sha256/header verification "
                   "(deleted, recompiled — never served)",
        "evicted": "entries removed by the LRU size-cap GC",
        "bypass": "compiles that could not be serialized on this "
                  "backend (executed fine, just not persisted)",
    }
    return _metrics.counter(f"{_METRIC_PREFIX}.{name}", docs.get(name, ""))


def _versions() -> Tuple[str, str, str, str]:
    import jax
    import jaxlib
    try:
        dev = jax.devices()[0]
        backend = f"{dev.platform}:{dev.device_kind}:{jax.device_count()}"
    except Exception:           # backend not initialized / unreachable
        backend = "unknown"
    return (jax.__version__, jaxlib.__version__,
            getattr(jax, "default_backend", lambda: "?")(), backend)


def fingerprint_lowered(lowered, extra=()) -> str:
    """Content fingerprint of a ``jax.stages.Lowered``: the StableHLO
    module text (shapes, dtypes, shardings, donation — the whole
    program) plus the jax/jaxlib/backend versions and any caller extra
    key.  Deterministic across processes for identical traces."""
    h = hashlib.sha256()
    h.update(lowered.as_text().encode())
    for part in _versions():
        h.update(part.encode())
        h.update(b"\0")
    h.update(repr(extra).encode())
    return h.hexdigest()


class ArtifactStore:
    """One on-disk store rooted at ``root``; safe for concurrent use
    from threads of one process and from cooperating processes (atomic
    tmp+rename writes; the index tolerates lost races because blobs are
    self-verifying)."""

    def __init__(self, root: str, max_bytes: Optional[int] = None,
                 name: str = "store"):
        self.root = os.path.abspath(root)
        self.name = name
        if max_bytes is None:
            mb = int(_flags.get_flag("FLAGS_aot_store_max_mb"))
            max_bytes = mb << 20 if mb > 0 else 0
        self.max_bytes = int(max_bytes)
        # lazy: the global store is constructed at import when
        # FLAGS_compile_cache_dir arrives via env
        self._lock = _conc.Lock(name="aot_store.index", lazy=True)
        os.makedirs(os.path.join(self.root, "objects"), exist_ok=True)

    # -- paths / index -------------------------------------------------
    def _obj_path(self, fp: str) -> str:
        return os.path.join(self.root, "objects", fp[:2], fp + ".bin")

    @property
    def _index_path(self) -> str:
        return os.path.join(self.root, "index.json")

    def _load_index(self) -> dict:
        try:
            with open(self._index_path, "rb") as f:
                idx = json.loads(f.read().decode())
            return idx if isinstance(idx, dict) else {}
        except (OSError, ValueError):
            return {}

    def _write_index(self, idx: dict, durable: bool = True):
        """Atomic index rewrite; ``durable=False`` skips the fsync for
        bookkeeping-only updates (LRU timestamps) — losing one to a
        crash costs an eviction-order approximation, nothing else."""
        tmp = self._index_path + f".tmp.{os.getpid()}"
        try:
            with open(tmp, "w") as f:
                json.dump(idx, f, sort_keys=True)
                if durable:
                    f.flush()
                    os.fsync(f.fileno())
            os.replace(tmp, self._index_path)
        except OSError:
            try:
                os.unlink(tmp)
            except OSError:
                pass

    # -- public surface ------------------------------------------------
    def __len__(self):
        n = 0
        objects = os.path.join(self.root, "objects")
        for _r, _d, files in os.walk(objects):
            n += sum(1 for f in files if f.endswith(".bin"))
        return n

    def get(self, fp: str):
        """Deserialize-and-load the entry for ``fp``; None on miss.
        Corrupt/mismatched entries are deleted and counted, never
        served; an intact entry that fails to load raises."""
        path = self._obj_path(fp)
        try:
            with open(path, "rb") as f:
                blob = f.read()
        except OSError:
            return None
        try:
            if not blob.startswith(_MAGIC):
                raise ValueError("bad magic")
            nl = blob.index(b"\n", len(_MAGIC))
            header = json.loads(blob[len(_MAGIC):nl].decode())
            payload = blob[nl + 1:]
            if len(payload) != int(header["size"]) or \
                    hashlib.sha256(payload).hexdigest() != header["sha256"]:
                raise ValueError("payload hash/size mismatch")
            jax_v, jaxlib_v, _plat, backend = _versions()
            if header.get("jax") != jax_v or \
                    header.get("jaxlib") != jaxlib_v or \
                    header.get("backend") != backend:
                raise ValueError(
                    f"version mismatch (entry {header.get('jax')}/"
                    f"{header.get('jaxlib')}/{header.get('backend')} vs "
                    f"running {jax_v}/{jaxlib_v}/{backend})")
            serialized, in_tree, out_tree = pickle.loads(payload)
            devices = [int(i) for i in header["devices"]]
        except (ValueError, KeyError, TypeError, EOFError,
                pickle.UnpicklingError):
            # a damaged or foreign entry is a clean miss
            _m("corrupt").inc()
            try:
                os.unlink(path)
            except OSError:
                pass
            with self._lock:
                idx = self._load_index()
                if idx.pop(fp, None) is not None:
                    self._write_index(idx)
            return None
        from jax.experimental import serialize_executable as _se
        # load onto the devices the executable was compiled for — the
        # default (every device of the backend) makes a one-device
        # program expect one shard per visible device.  An intact entry
        # that will not load is a defect of the store, not a miss: raise.
        by_id = {d.id: d for d in jax.devices()}
        exe = _se.deserialize_and_load(
            serialized, in_tree, out_tree,
            execution_devices=[by_id[i] for i in devices])
        with self._lock:        # LRU bookkeeping (best-effort)
            idx = self._load_index()
            ent = idx.get(fp) or {"size": len(blob)}
            ent["last_used"] = time.time()
            idx[fp] = ent
            self._write_index(idx, durable=False)
        return exe

    def put(self, fp: str, compiled, label: str = "") -> bool:
        """Serialize ``compiled`` under ``fp`` (atomic write + GC).
        Returns False (counted ``bypass``) when the backend can't
        serialize this executable; never raises into the caller."""
        try:
            from jax.experimental import serialize_executable as _se
            payload = pickle.dumps(_se.serialize(compiled), protocol=4)
        except Exception:       # noqa: BLE001 — persistence is optional
            _m("bypass").inc()
            return False
        jax_v, jaxlib_v, _plat, backend = _versions()
        header = json.dumps({
            "sha256": hashlib.sha256(payload).hexdigest(),
            "size": len(payload), "jax": jax_v, "jaxlib": jaxlib_v,
            "backend": backend, "label": label, "fingerprint": fp,
            "devices": [d.id for d in
                        compiled.runtime_executable().local_devices()],
        }, sort_keys=True).encode()
        blob = _MAGIC + header + b"\n" + payload
        path = self._obj_path(fp)
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = path + f".tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                f.write(blob)
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            _m("bypass").inc()
            return False
        with self._lock:
            idx = self._load_index()
            idx[fp] = {"size": len(blob), "last_used": time.time(),
                       "label": label}
            self._gc_locked(idx, keep=fp)
            self._write_index(idx)
        _m("store").inc()
        return True

    def _gc_locked(self, idx: dict, keep: str):
        """Evict least-recently-used entries past ``max_bytes`` (never
        the entry just written).  Sizes and the candidate set come from
        the objects dir itself, not the index, so blobs orphaned by a
        crash between blob write and index write still count against
        the cap and still get evicted (their LRU stamp falls back to
        file mtime)."""
        if not self.max_bytes:
            return
        on_disk = {}
        objects = os.path.join(self.root, "objects")
        for root, _dirs, files in os.walk(objects):
            for f in files:
                if not f.endswith(".bin"):
                    continue
                path = os.path.join(root, f)
                try:
                    st = os.stat(path)
                except OSError:
                    continue
                on_disk[f[:-len(".bin")]] = (path, st.st_size,
                                             st.st_mtime)
        total = sum(size for _p, size, _mt in on_disk.values())
        if total <= self.max_bytes:
            return
        order = sorted(
            (idx.get(fp, {}).get("last_used", mtime), fp)
            for fp, (_path, _size, mtime) in on_disk.items()
            if fp != keep)
        for _ts, fp in order:
            if total <= self.max_bytes:
                break
            path, size, _mt = on_disk[fp]
            total -= size
            idx.pop(fp, None)
            try:
                os.unlink(path)
            except OSError:
                pass
            _m("evicted").inc()

    def load_or_compile(self, lowered, label: str = "", extra=()):
        """THE entry point: return a ready executable for ``lowered``,
        from the store when possible, compiling (and persisting) when
        not.  Always returns a callable executable."""
        from ..profiler import memscope as _memscope
        fp = fingerprint_lowered(lowered, extra)
        t0 = time.perf_counter() if _memscope.active else 0.0
        exe = self.get(fp)
        if exe is not None:
            _m("hit").inc()
            if _memscope.active:
                _memscope.compile_record(
                    label or "aot", fp, time.perf_counter() - t0,
                    provenance="store-hit", cause="cached")
            return exe
        _m("miss").inc()
        hits0 = _jax_cache_hits()
        compiled = lowered.compile()
        if _memscope.active:
            _memscope.compile_record(
                label or "aot", fp, time.perf_counter() - t0,
                provenance="store-miss")
        if _jax_cache_hits() != hits0:
            # JAX's persistent cache served this compile: it is already
            # persistent there, and XLA:CPU cannot re-serialize an
            # executable it loaded from that cache (the blob would fail
            # at run time with NOT_FOUND)
            _m("bypass").inc()
        else:
            self.put(fp, compiled, label=label)
        return compiled


# ---------------------------------------------------------------------------
# module-level store, armed from FLAGS_compile_cache_dir
# ---------------------------------------------------------------------------
_state = {"store": None, "root": None}


def configure() -> Optional[ArtifactStore]:
    """(Re)arm the global store under
    ``<FLAGS_compile_cache_dir>/artifacts``; no-op when the flag is
    empty or unchanged.  Called at import and from the flags
    observer."""
    d = _flags.get_flag("FLAGS_compile_cache_dir") or ""
    root = os.path.join(os.path.abspath(d), "artifacts") if d else None
    if root == _state["root"]:
        return _state["store"]
    if root is None:
        _state["store"] = None
        _state["root"] = None
        return None
    try:
        _state["store"] = ArtifactStore(root)
        _state["root"] = root
    except OSError:
        _state["store"] = None
        _state["root"] = None
    return _state["store"]


def active() -> Optional[ArtifactStore]:
    """The armed global store, or None (flag empty)."""
    return _state["store"]


def aot_compile(lowered, label: str = "", extra=()):
    """``lowered.compile()`` through the global artifact store when one
    is armed — every AOT compile site in the framework funnels through
    here so a single flag warms them all."""
    store = active()
    if store is None:
        from ..profiler import memscope as _memscope
        if _memscope.active:
            t0 = time.perf_counter()
            exe = lowered.compile()
            _memscope.compile_record(
                label or "aot", fingerprint_lowered(lowered, extra),
                time.perf_counter() - t0, provenance="no-store")
            return exe
        return lowered.compile()
    return store.load_or_compile(lowered, label=label, extra=extra)


def stats() -> dict:
    """Hit/miss/store/corrupt counters (for CI gates and tests)."""
    from ..profiler import metrics as _metrics
    out = {}
    for k in ("hit", "miss", "store", "corrupt", "evicted", "bypass"):
        c = _metrics.get(f"{_METRIC_PREFIX}.{k}")
        out[k] = c.value if c is not None else 0
    return out


_flags.on_change(configure)
configure()
