"""Runtime flag registry.

Reference parity: ``paddle/fluid/platform/flags.cc:48ff``
(PADDLE_DEFINE_EXPORTED_* gflags) + Python ``get/set_flags``.  Flags are
importable from env (FLAGS_x=1 python ...) and settable at runtime.
"""
from __future__ import annotations

import os
import threading
from typing import Any, Dict

__all__ = ["define_flag", "get_flag", "set_flags", "get_flags", "all_flags"]

_lock = threading.Lock()
_FLAGS: Dict[str, Any] = {}
_DOC: Dict[str, str] = {}


def _env_cast(raw: str, default):
    if isinstance(default, bool):
        return raw.lower() in ("1", "true", "yes", "on")
    if isinstance(default, int):
        return int(raw)
    if isinstance(default, float):
        return float(raw)
    return raw


def define_flag(name: str, default, doc: str = ""):
    with _lock:
        raw = os.environ.get(name)
        _FLAGS[name] = _env_cast(raw, default) if raw is not None else default
        _DOC[name] = doc


def get_flag(name: str):
    try:
        return _FLAGS[name]
    except KeyError:
        raise KeyError(f"unknown flag '{name}'") from None


def set_flags(flags: Dict[str, Any]):
    with _lock:
        for k, v in flags.items():
            if k not in _FLAGS:
                raise KeyError(f"unknown flag '{k}'")
            _FLAGS[k] = v
    _refresh_debug_cache()
    for fn in _observers:
        fn()


# modules that cache flag-derived fast paths (chaos registry, ...)
# register a refresher here; set_flags invokes each after an update
_observers = []


def on_change(fn):
    _observers.append(fn)


# cached fast-path predicate for the per-op dispatch hot loop: one module
# attribute read when the debug flags are all off
debug_ops_active = False


def _refresh_debug_cache():
    global debug_ops_active
    debug_ops_active = bool(_FLAGS.get("FLAGS_check_nan_inf") or
                            _FLAGS.get("FLAGS_benchmark"))


def get_flags(names):
    if isinstance(names, str):
        names = [names]
    return {n: get_flag(n) for n in names}


def all_flags() -> Dict[str, Any]:
    return dict(_FLAGS)


# -- core flag set (subset of platform/flags.cc most relevant on TPU) ------
define_flag("FLAGS_eager_jit_cache", True,
            "cache jitted fwd/vjp per (op, closure, shapes) on the eager "
            "tape path (dygraph speed; SURVEY hard part a)")
define_flag("FLAGS_use_pallas", True,
            "prefer hand-written pallas kernels on TPU where registered")
define_flag("FLAGS_check_nan_inf", False,
            "check every op output for nan/inf (debug; reference "
            "framework/details/nan_inf_utils_detail.cc)")
define_flag("FLAGS_allocator_strategy", "auto_growth",
            "kept for API parity; PJRT owns TPU HBM allocation")
define_flag("FLAGS_benchmark", False,
            "block_until_ready after every op for timing accuracy")
define_flag("FLAGS_cudnn_deterministic", False, "parity no-op on TPU")
define_flag("FLAGS_max_inplace_grad_add", 0, "parity no-op")
define_flag("FLAGS_init_allocated_mem", False, "parity no-op")
define_flag("FLAGS_default_dtype", "float32", "default floating dtype")
define_flag("FLAGS_matmul_precision", "default",
            "jax matmul precision: default|high|highest")
define_flag("FLAGS_log_recompile", False,
            "announce Executor program recompiles on new feed "
            "signatures (each new shape compiles a new XLA program)")
define_flag("FLAGS_check_program", False,
            "run the static-analysis pass bundle (verifier + shape "
            "inference with real feed shapes) on every new Executor "
            "compile; malformed programs raise "
            "ProgramVerificationError naming the op and var instead of "
            "failing inside jax.jit (reference: per-OpDesc InferShape/"
            "verification at compile time)")
define_flag("FLAGS_program_dce", True,
            "apply the dead_op_eliminate ir pass when running a "
            "CompiledProgram: ops reaching neither a fetch target nor a "
            "parameter/state update are stripped before compile "
            "(bit-exact; saves trace+XLA-compile time per feed "
            "signature)")
define_flag("FLAGS_program_opt", True,
            "run the optimizing ir passes (constant_fold, cse, "
            "fusion_group — static/passes/optimize.py) when running a "
            "CompiledProgram: const-only subgraphs evaluate at pass "
            "time, duplicate pure ops merge, and contiguous "
            "elementwise chains dispatch as one fused region "
            "(bit-exact by construction; version-keyed cached like "
            "FLAGS_program_dce)")
define_flag("FLAGS_program_opt_skip", "",
            "comma-separated optimizing pass names to skip while "
            "FLAGS_program_opt stays on, e.g. 'constant_fold,cse' "
            "leaves only fusion_group active")
define_flag("FLAGS_aot_store_max_mb", 2048,
            "size cap (MiB) of the content-addressed AOT artifact "
            "store (<FLAGS_compile_cache_dir>/artifacts); "
            "least-recently-used executables are evicted past it, "
            "0 disables the cap (utils/artifact_store.py)")
define_flag("FLAGS_host_tracer_capacity", 1 << 20,
            "max host spans held by the profiler ring buffer; oldest "
            "spans drop beyond this (reference host_trace_level buffer)")
define_flag("FLAGS_chaos_spec", "",
            "deterministic fault-injection spec, e.g. "
            "'ckpt.write:fail@3;store.rpc:delay=0.5@2-4' — named sites "
            "(ckpt.write, store.rpc, store.partition, fs.rename, "
            "loader.worker, step.loss, host.slow, serve.request, "
            "kv.block_alloc, router.dispatch, fleet.lease, ps.pull, "
            "ps.push, ps.shard_down) fail/stall/poison on a seeded "
            "schedule; empty means every site costs one predicate read "
            "(utils/chaos.py)")
define_flag("FLAGS_chaos_seed", 0,
            "seed for probabilistic chaos selectors (p=...); same seed "
            "+ same call pattern = same injection schedule")
define_flag("FLAGS_watchdog_timeout", 60.0,
            "supervisor mode (distributed.launch --supervise): a worker "
            "whose heartbeat step has not advanced for this many "
            "seconds is declared hung; the gang is killed and "
            "relaunched (TorchElastic-style supervised restart)")
define_flag("FLAGS_inference_retrace_warn", 8,
            "warn once when a Predictor (with its clones) has "
            "jit-retraced for more than this many distinct input-shape "
            "signatures — every novel shape pays a full XLA compile; "
            "serving's shape bucketing bounds this "
            "(paddle_tpu/serving/bucketing.py)")
define_flag("FLAGS_serving_queue_depth", 128,
            "default InferenceEngine admission bound: requests waiting "
            "beyond this depth are rejected with RequestRejected "
            "(shed, don't OOM); per-engine override via "
            "EngineConfig.max_queue")
define_flag("FLAGS_anomaly_action", "",
            "hapi Model.fit guard on nan/inf loss: '' (off, keeps the "
            "lazy-loss pipeline), 'raise' (FloatingPointError at the "
            "producing step), 'skip' (revert this step's update and "
            "continue), 'rollback' (restore the newest intact "
            "checkpoint when fit(checkpointer=...) is set, else skip)")
define_flag("FLAGS_compile_cache_dir", "",
            "root of the AOT artifact store "
            "(<dir>/artifacts, utils/artifact_store.py): relaunches "
            "deserialize persisted executables instead of compiling; "
            "empty leaves the store off.  JAX's own persistent "
            "compilation cache is NOT placed by this flag: it follows "
            "JAX_COMPILATION_CACHE_DIR, else <repo>/.jax_cache "
            "(utils/compile_cache.py)")
define_flag("FLAGS_lock_san", 0,
            "runtime lock sanitizer level for the framework's named "
            "locks (utils/concurrency.py): 0 = off (factories return "
            "plain threading primitives; zero per-acquire cost), 1 = "
            "instrument — per-thread held-lock stacks, a process-global "
            "acquisition-order graph that WARNS when an acquire closes "
            "an ordering cycle (potential deadlock), per-site "
            "lock.wait_ms/lock.hold_ms histograms, long-hold warnings "
            "— 2 = same but cycle formation RAISES LockOrderError at "
            "the offending acquire (CI gates).  Read once at lock "
            "construction, so set it via env or before building "
            "engines/loaders/checkpointers")
define_flag("FLAGS_lock_hold_warn_ms", 200.0,
            "with FLAGS_lock_san >= 1: warn (and count "
            "lock.long_hold) when any sanitizer lock is held longer "
            "than this many milliseconds — long critical sections "
            "serialize every waiter under load; 0 disables the check")
define_flag("FLAGS_straggler_factor", 3.0,
            "supervisor straggler detection (distributed.launch "
            "--supervise): a rank whose rolling median per-step wall "
            "time (reported in heartbeat payloads) exceeds this factor "
            "x the gang median (median of the OTHER ranks' medians) "
            "accrues one strike per fresh heartbeat sample; 0 disables "
            "detection entirely")
define_flag("FLAGS_straggler_patience", 3,
            "consecutive straggler strikes before a rank is reported "
            "(launch.straggler metric + supervise report JSON) and — "
            "under launch --evict_stragglers — the gang is re-formed "
            "without that host via a rendezvous denylist entry")
define_flag("FLAGS_fused_conv", True,
            "dispatch conv+batch_norm+activation blocks as ONE fused op "
            "(ops/fused_conv.py): training mode runs conv -> fold BN "
            "scale/shift -> activation in a single jitted call whose "
            "custom_vjp backward recomputes the cheap epilogue instead "
            "of saving normalized/mask intermediates; inference mode "
            "folds the BN constants into the conv weights.  Adopted by "
            "the vision conv models behind nn.functional.fused_conv_bn; "
            "0 falls back to the eager conv/bn/act composition "
            "(bit-parity-pinned by tests/test_fused_conv.py)")
define_flag("FLAGS_fused_optimizer", True,
            "apply Momentum/Adam/AdamW updates as one fused kernel per "
            "stacked same-shape parameter group instead of one dispatch "
            "per leaf (optimizer/fused_update.py): parameters sharing "
            "(shape, dtype, decay config) stack into a (G, ...) array "
            "and update under jax.vmap — per-element math identical to "
            "the per-leaf loop (bit-parity-pinned), dispatched-op count "
            "drops from O(params) to O(groups).  0 restores the "
            "per-leaf reference path")
define_flag("FLAGS_conv_bn_fold", False,
            "static-program pass: rewrite eval-form conv->batch_norm"
            "(->relu) chains into the folded-constant inference form "
            "(BN scale/shift folded into the conv weights — one conv + "
            "bias instead of conv + normalize).  Changes rounding "
            "(tolerance-level, not bit-exact), so it is OFF by default "
            "and excluded from the FLAGS_program_opt bit-exact "
            "pipeline; serving programs opt in for the latency win")
define_flag("FLAGS_kv_cache_dtype", "float32",
            "storage dtype of the paged KV-cache arenas "
            "(generation/paged_kv.py): 'float32' (exact) or 'int8' "
            "(per-token-per-head scales, dequantized inside the "
            "attention executable — ~3.6x less HBM per block at a pinned "
            "top-1/bitstream-tolerance gate).  Read by "
            "GenerationEngineConfig at construction")
define_flag("FLAGS_prefix_cache_blocks", 0,
            "capacity (in KV blocks) of the content-addressed prefix "
            "cache (generation/prefix_cache.py): sha256-keyed chains "
            "of filled, refcounted, immutable blocks so shared system "
            "prompts prefill once and hit forever; LRU-evicted past "
            "this cap.  0 disables the cache (engines can still opt "
            "in via GenerationEngineConfig.prefix_cache_blocks)")
define_flag("FLAGS_speculative_k", 0,
            "draft tokens proposed per decode step by the n-gram "
            "prompt-lookup drafter (generation/speculative.py); one "
            "batched verify executable accepts the longest agreeing "
            "prefix, so accepted spans multiply tokens/s per stream "
            "with a greedy-equivalence guarantee.  0 disables "
            "speculative decoding (engines can opt in via "
            "GenerationEngineConfig.speculative_k)")
define_flag("FLAGS_request_trace", False,
            "per-request distributed tracing (profiler/rtrace.py): "
            "serving requests carry a TraceContext (128-bit trace_id, "
            "W3C traceparent parsed from and echoed on HTTP requests) "
            "and the engines record ingress->admission->queue->prefill->"
            "decode->egress spans into the chrome-trace ring, with one "
            "batch-step span linked to every member request (fan-in "
            "causality).  Off (the default) costs one predicate read "
            "per hop; tools/trace_summary.py --request <id> renders "
            "the per-request waterfall")
define_flag("FLAGS_mem_accounting", False,
            "device-memory accounting + goodput telemetry "
            "(profiler/memscope.py): tagged live-byte attribution "
            "(params / opt_state / kv_arena / prefix_cache / "
            "activations / prefetch) via a live-array census, "
            "per-step-phase peak watermarks, a compile/retrace ledger "
            "with cause + artifact-store provenance, Model.fit "
            "goodput fractions (train.goodput.* gauges, folded into "
            "PADDLE_SUPERVISE_REPORT), and RESOURCE_EXHAUSTED "
            "forensics dumps (census + pool occupancy + flight ring "
            "into PADDLE_FLIGHT_DIR, then the error re-raises).  Off "
            "(the default) costs one predicate read per hook")
define_flag("FLAGS_flight_recorder", True,
            "always-on flight recorder (profiler/flight.py): a "
            "lock-free bounded ring of structured events (admission "
            "verdicts, slot admit/retire, kv sheds, chaos injections, "
            "checkpoint commits, rendezvous rounds, lock-san cycles, "
            "anomaly trips) dumped as JSON on crash/watchdog/SIGUSR1/"
            "engine failure so every post-mortem ends with the last N "
            "things the process actually did.  0 disables: every site "
            "then costs one predicate read")
define_flag("FLAGS_flight_recorder_capacity", 2048,
            "events held by the flight-recorder ring; the oldest drop "
            "beyond this, so the recorder can stay armed for the whole "
            "life of a serving process")
define_flag("FLAGS_program_remat", False,
            "run the rematerialization policy pass (program_remat, "
            "static/passes/remat.py) when running a CompiledProgram: "
            "the static memory planner's liveness timeline picks "
            "forward subchains whose activations are recomputed in the "
            "backward pass (jax.checkpoint) instead of held across it. "
            "Bit-exact (same primitives replayed in the same order); "
            "only active when FLAGS_remat_budget_mb > 0")
define_flag("FLAGS_remat_budget_mb", 0,
            "peak-HBM byte budget (MiB) the program_remat pass "
            "rewrites toward: chains are rematerialized greedily by "
            "estimated saving until the planner's peak estimate fits "
            "the budget or no eligible chain remains.  0 (the default) "
            "makes program_remat a no-op even when FLAGS_program_remat "
            "is set")
define_flag("FLAGS_prefetch_to_device", 2,
            "default device-prefetch depth used by Model.fit's train "
            "loop (batches kept resident on device by the io "
            "DevicePrefetcher background thread; double-buffered at "
            "2).  0 disables the async input pipeline; per-loader "
            "override via DataLoader(prefetch_to_device=N)")

# flags may arrive via env at import time — seed the dispatch fast path
_refresh_debug_cache()
