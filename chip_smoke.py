#!/usr/bin/env python3
"""chip_smoke.py — the train, fit and serve paths, once, on the chip.

    python3 chip_smoke.py        (run it on the TPU machine: chiprun -- ...)

One process, phases in order, the first failure ends the run with a
non-zero exit code and no result line:

  device     JAX must report a TPU; versions and the compile cache in force
  kernels    every ops/pallas entry, compiled (never interpreted), forward
             and backward, against the plain jnp reference the unit tests use
  train      build_spmd_train_step at the recorded GPT cell's exact shape;
             the lowered program must hold its Mosaic calls (2 per layer + head)
  fit        paddle.Model(resnet50).fit over DataLoader worker processes
             and the default device prefetcher, bf16 O2
  serve      GPT -> PagedGenerationEngine -> ServingServer on localhost, a few
             /v1/generate requests checked against the in-process reference
  multichip  with four devices: the same width on dp2 x mp2 and pp2 x mp2
             (1F1B) against the one-chip loss; otherwise printed as skipped

It proves that the paths run and that what comes out is right.  It times
nothing but its own set-up (compile seconds per phase, to show the
persistent cache serving a second run) and prints no rate, utilization or
peak.  It never sets JAX_PLATFORMS or a cache directory.

The last line of stdout is the result, one JSON object with exactly
these keys, the device as JAX reports it:
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}
The full report ({"ok", "device", "phases": {...}, "compile_cache",
"wall_s", "claim": null}) is the "[report]" line before it and the file
chiprun_out/chip_smoke.json.

The phases are importable functions taking sizes; tests/test_chip_smoke.py
rehearses them at toy sizes on the CPU (main() has no CPU mode).
"""
import json
import math
import os
import sys
import time

# the recorded GPT cell (BERT-base width) and the smoke's kernel shapes —
# tests/test_tpu_aot_compile.py compiles the same list for a v5e without one
GPT_DIMS = dict(vocab_size=30528, hidden_size=768, num_layers=12,
                num_heads=12, max_seq_len=512)
# (batch, seq, expected regime): H=12, d=64, causal, bf16
ATTN_SHAPES = [(8, 512, "packed_small"), (2, 2048, "packed_mid"),
               (1, 4096, "mid"), (1, 8192, "stream")]
ATTN_HEADS, ATTN_HEAD_DIM = 12, 64
XENT_SHAPE = (8192, 768, 30528)                      # rows, D, V
# (rows, D, dropout p): p = 0 and p > 0, and a row count that is no
# multiple of the 8-row sublane tile
LN_SHAPES = [(4096, 768, 0.0), (4096, 768, 0.1), (100, 768, 0.1)]


class SmokeFailure(AssertionError):
    pass


def check(cond, msg):
    if not cond:
        raise SmokeFailure(msg)


def say(phase, msg):
    print(f"[{phase}] {msg}", flush=True)


# ---------------------------------------------------------------------------
# set-up accounting: compile seconds and persistent-cache traffic per phase
# ---------------------------------------------------------------------------
class CompileMeter:
    """Sums JAX's own compile events (backend compile, which on a warm
    run is the persistent-cache retrieval) between ``take()`` calls."""

    def __init__(self):
        from jax import monitoring
        self.secs, self.hits, self.misses = 0.0, 0, 0
        monitoring.register_event_duration_secs_listener(self._on_secs)
        monitoring.register_event_listener(self._on_event)

    def _on_secs(self, event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.secs += secs

    def _on_event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def take(self):
        out = {"compile_s": round(self.secs, 2), "cache_hits": self.hits,
               "cache_misses": self.misses}
        self.secs, self.hits, self.misses = 0.0, 0, 0
        return out


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def phase_device(require="tpu"):
    import jax
    import jaxlib
    from paddle_tpu.utils import compile_cache
    dev = jax.devices()[0]
    info = {"platform": dev.platform, "kind": dev.device_kind,
            "count": len(jax.devices())}
    try:
        import libtpu
        libtpu_v = getattr(libtpu, "__version__", "unknown")
    except ImportError:
        libtpu_v = "absent"
    cache = compile_cache.cache_dir()
    say("device", f"platform={info['platform']} kind={info['kind']!r} "
        f"count={info['count']} jax={jax.__version__} "
        f"jaxlib={jaxlib.__version__} libtpu={libtpu_v}")
    say("device", f"compile cache dir={cache} "
        f"entries={compile_cache.entry_count()} "
        f"(JAX_COMPILATION_CACHE_DIR "
        f"{'set' if os.environ.get('JAX_COMPILATION_CACHE_DIR') else 'unset'})")
    check(info["platform"] == require,
          f"JAX found no {require}: platform={info['platform']!r}")
    return info


# ---------------------------------------------------------------------------
# kernels
# ---------------------------------------------------------------------------
def _ref_attention_qkv(qkv, num_heads, causal):
    """Plain f32 attention over the packed projection output, one head
    at a time and recomputed in the backward (one (T, T) score block
    live at once keeps T=8192 in memory)."""
    import jax
    import jax.numpy as jnp
    B, T, F3 = qkv.shape
    d = F3 // 3 // num_heads
    x = qkv.astype(jnp.float32).reshape(B, T, 3, num_heads, d)
    x = jnp.moveaxis(x, 3, 0)                        # (H, B, T, 3, d)

    def one_head(h):
        q, k, v = h[:, :, 0], h[:, :, 1], h[:, :, 2]
        s = jnp.einsum("bqd,bkd->bqk", q, k) / math.sqrt(d)
        if causal:
            s = jnp.where(jnp.tril(jnp.ones((T, T), bool)), s, -1e30)
        return jnp.einsum("bqk,bkd->bqd", jax.nn.softmax(s, -1), v)

    out = jax.lax.map(jax.checkpoint(one_head), x)   # (H, B, T, d)
    return jnp.moveaxis(out, 0, 2).reshape(B, T, num_heads * d)


def _rel_err(got, want):
    import jax.numpy as jnp
    got, want = got.astype(jnp.float32), want.astype(jnp.float32)
    return float(jnp.max(jnp.abs(got - want))
                 / jnp.maximum(jnp.max(jnp.abs(want)), 1e-30))


def _rand(rs, *shape, dtype="bfloat16"):
    import jax.numpy as jnp
    return jnp.asarray(rs.rand(*shape), dtype)


def kernels_attention(B, T, regime, heads, head_dim, impl):
    """flash attention forward and backward at one shape, through the
    stacked entry the GPT block calls; the regime selected is part of
    the check."""
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas.flash_attention import flash_attention_stacked
    rs = np.random.RandomState(T)
    qkv = _rand(rs, B, T, 3 * heads * head_dim)
    g = _rand(rs, B, T, heads * head_dim)
    key = f"flash_attention.{regime}.{impl}"
    ref, ref_vjp = jax.vjp(jax.jit(functools.partial(
        _ref_attention_qkv, num_heads=heads, causal=True)), qkv)
    (ref_d,) = ref_vjp(g.astype(jnp.float32))

    def stacked(a):                    # q/k/v axis first, as the block
        return flash_attention_stacked(
            jnp.moveaxis(a.reshape(B, T, 3, -1), 2, 0),
            num_heads=heads, causal=True)

    before = pallas.selections().get(key, 0)
    out, vjp = jax.vjp(jax.jit(stacked), qkv)
    (dqkv,) = vjp(g)
    check(pallas.selections().get(key, 0) > before,
          f"T={T}: expected selection {key}, got {pallas.selections()}")
    check(out.dtype == qkv.dtype and dqkv.dtype == qkv.dtype,
          f"T={T}: kernel left {qkv.dtype}")
    e_out, e_d = _rel_err(out, ref), _rel_err(dqkv, ref_d)
    check(e_out < 2e-2 and e_d < 5e-2,
          f"flash attention T={T} ({regime}) off the reference: "
          f"out {e_out:.2e} dqkv {e_d:.2e}")
    say("kernels", f"flash_attention_stacked {regime} B={B} T={T} "
        f"H={heads} d={head_dim} bf16 causal fwd+bwd ok "
        f"(err out {e_out:.1e}, dqkv {e_d:.1e})")


def kernels_xent(N, D, V, impl):
    """The fused softmax-xent head (and its dlogits kernel) against
    whole-logits jnp math."""
    import functools
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops.pallas import softmax_xent as sx
    interpret = impl == "interpret"
    rs = np.random.RandomState(1)
    x = jnp.asarray(rs.randn(N, D), jnp.bfloat16)
    w = jnp.asarray(rs.randn(D, V) * 0.02, jnp.bfloat16)
    lab = jnp.asarray(rs.randint(0, V, (N,)), jnp.int32)

    def ref_loss(x, w):
        logits = x.astype(jnp.float32) @ w.astype(jnp.float32)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        return jnp.mean(lse - jnp.take_along_axis(
            logits, lab[:, None], 1)[:, 0])

    loss, (dx, dw) = jax.jit(jax.value_and_grad(
        lambda x, w: sx.softmax_xent_loss(x, w, lab, interpret),
        (0, 1)))(x, w)
    rloss, (rdx, rdw) = jax.jit(jax.value_and_grad(ref_loss, (0, 1)))(x, w)
    e_l = abs(float(loss) - float(rloss)) / abs(float(rloss))
    e_dx, e_dw = _rel_err(dx, rdx), _rel_err(dw, rdw)
    check(e_l < 1e-2 and e_dx < 5e-2 and e_dw < 5e-2,
          f"softmax_xent_loss off the reference: loss {e_l:.2e} "
          f"dx {e_dx:.2e} dw {e_dw:.2e}")
    say("kernels", f"softmax_xent_loss N={N} D={D} V={V} bf16 fwd+bwd ok "
        f"(err loss {e_l:.1e}, dx {e_dx:.1e}, dw {e_dw:.1e})")
    rows = min(N, 1024)
    logits = x[:rows].astype(jnp.float32) @ w.astype(jnp.float32)
    lse = jax.scipy.special.logsumexp(logits, axis=-1)
    dl = jax.jit(functools.partial(sx.softmax_xent_dlogits,
                                   interpret=interpret))(
        x[:rows], w, lab[:rows], lse, 2.0)
    want = (jax.nn.softmax(logits, -1) - jax.nn.one_hot(lab[:rows], V)) * 2
    e_dl = _rel_err(dl[:, :V], want)
    check(e_dl < 2e-2 and not bool(jnp.any(dl[:, V:])),
          f"softmax_xent_dlogits off the reference: {e_dl:.2e}")
    say("kernels", f"softmax_xent_dlogits rows={rows} ok (err {e_dl:.1e})")


def kernels_fused_ln(N, D, p, impl):
    """fused bias + dropout + residual + layernorm: the kernel against
    ``_fused_math``, forward and backward."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from paddle_tpu.ops import fused_ops, pallas
    rs = np.random.RandomState(N)
    a, r = (jnp.asarray(rs.randn(N, D), jnp.float32) for _ in range(2))
    b, be = (jnp.asarray(rs.randn(D), jnp.float32) for _ in range(2))
    ga = jnp.asarray(rs.rand(D) + 0.5, jnp.float32)
    seed = jnp.asarray(1234, jnp.uint32)

    def run(use_pallas):
        def f(a, r, b, ga, be):
            return fused_ops._fused(a, r, b, ga, be, seed, p, 1e-5,
                                    use_pallas)
        out, vjp = jax.vjp(jax.jit(f), a, r, b, ga, be)
        return (out,) + vjp(jnp.ones_like(out))

    before = pallas.selections().get(f"fused_ln.{impl}", 0)
    got, want = run(True), run(False)
    check(pallas.selections().get(f"fused_ln.{impl}", 0) > before,
          f"fused_ln did not run as {impl}: {pallas.selections()}")
    # one differing dropout bit moves a whole row by O(1): this
    # tolerance also proves kernel and math drew the same mask
    errs = [_rel_err(g_, w_) for g_, w_ in zip(got, want)]
    check(max(errs) < 1e-4, f"fused_ln N={N} p={p} off _fused_math: {errs}")
    say("kernels", f"fused_ln N={N} D={D} p={p} fwd+bwd ok "
        f"(max err {max(errs):.1e})")


def phase_kernels(attn_shapes=ATTN_SHAPES, heads=ATTN_HEADS,
                  head_dim=ATTN_HEAD_DIM, xent_shape=XENT_SHAPE,
                  ln_shapes=LN_SHAPES, impl="mosaic"):
    """Each ops/pallas entry forward and backward against jnp math.
    ``impl`` is what every selection must have been: "mosaic" on the
    chip ("interpret" in the CPU rehearsal under PADDLE_PALLAS_FORCE=1)."""
    from paddle_tpu.ops import pallas
    for B, T, regime in attn_shapes:
        kernels_attention(B, T, regime, heads, head_dim, impl)
    kernels_xent(*xent_shape, impl)
    for N, D, p in ln_shapes:
        kernels_fused_ln(N, D, p, impl)
    other = "interpret" if impl == "mosaic" else "mosaic"
    bad = {k: v for k, v in pallas.selections().items()
           if k.endswith("." + other)}
    check(not bad, f"kernels ran as {other}, not {impl}: {bad}")
    return {"selections": pallas.selections()}


# ---------------------------------------------------------------------------
# train
# ---------------------------------------------------------------------------
def _gpt_batch(vocab, B, T, seed=0):
    import jax.numpy as jnp
    import numpy as np
    rng = np.random.RandomState(seed)
    return (jnp.asarray(rng.randint(0, vocab, (B, T)), jnp.int32),
            jnp.asarray(rng.randint(0, vocab, (B, T)), jnp.int32))


def phase_train(dims=GPT_DIMS, batch=128, steps=8, mosaic_calls=None,
                dtype="bfloat16", remat="ctx", learning_rate=3e-4):
    """One compile plus ``steps`` steps of the flagship train step on a
    fixed batch.  ``mosaic_calls`` is the number of Mosaic custom calls
    the lowered program must hold (2 per layer + the fused head)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.models.gpt_spmd import build_spmd_train_step
    cfg = GPTConfig(**dims)
    if mosaic_calls is None:
        mosaic_calls = 2 * cfg.num_layers + 1
    mesh = build_mesh({"dp": 1}, devices=jax.devices()[:1])
    step, init_fn = build_spmd_train_step(
        cfg, mesh, compute_dtype=jnp.dtype(dtype), remat_policy=remat,
        learning_rate=learning_rate)
    params, opt_state = init_fn(seed=0)
    ids, labels = _gpt_batch(cfg.vocab_size, batch, cfg.max_seq_len)
    n_mosaic = step.lower(params, opt_state, ids, labels).as_text() \
        .count("tpu_custom_call")
    check(n_mosaic == mosaic_calls,
          f"lowered train step holds {n_mosaic} Mosaic calls, expected "
          f"{mosaic_calls} — a kernel gave way to XLA math")
    losses = []
    for _ in range(steps):
        loss, params, opt_state = step(params, opt_state, ids, labels)
        losses.append(float(loss))
    jax.block_until_ready(params)
    ln_v = math.log(cfg.vocab_size)
    check(all(math.isfinite(v) for v in losses), f"loss not finite: {losses}")
    check(abs(losses[0] - ln_v) < 0.5,
          f"step-0 loss {losses[0]:.4f} not within 0.5 of ln V = {ln_v:.4f}")
    check(losses[-1] < losses[0], f"loss did not fall: {losses}")
    say("train", f"GPT L{cfg.num_layers}/D{cfg.hidden_size}/"
        f"T{cfg.max_seq_len} B={batch} {dtype} remat={remat}: "
        f"{n_mosaic} Mosaic calls, loss {losses[0]:.4f} -> "
        f"{losses[-1]:.4f} over {steps} steps (ln V = {ln_v:.4f})")
    return {"mosaic_calls": n_mosaic, "losses": [round(v, 4) for v in losses]}


# ---------------------------------------------------------------------------
# fit
# ---------------------------------------------------------------------------
def phase_fit(image=224, batch=32, samples=64, epochs=4, classes=1000,
              workers=2, depth=50, amp="O2"):
    """Model.fit over worker processes (forked after JAX is up) and the
    default device prefetcher; the loss must be finite and fall."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.io import DataLoader, Dataset

    class Synthetic(Dataset):
        """In-memory images with labels from ten classes, made from a
        seed — a set the network can start to memorise in a few steps."""

        def __init__(self):
            rng = np.random.RandomState(0)
            self.x = rng.rand(samples, 3, image, image).astype("float32")
            self.y = rng.randint(0, min(10, classes), (samples, 1)) \
                .astype("int32")

        def __len__(self):
            return samples

        def __getitem__(self, i):
            return self.x[i], self.y[i]

    class Losses(paddle.callbacks.Callback):
        def __init__(self):
            super().__init__()
            self.values = []

        def on_train_batch_end(self, step, logs=None):
            self.values.append(float(logs["loss"]))   # must materialise

    paddle.seed(0)
    net = getattr(paddle.vision.models, f"resnet{depth}")(
        num_classes=classes)
    model = paddle.Model(net)
    opt = paddle.optimizer.Momentum(learning_rate=0.002, momentum=0.9,
                                    parameters=net.parameters())
    model.prepare(opt, paddle.nn.CrossEntropyLoss(), amp_configs=amp)
    loader = DataLoader(Synthetic(), batch_size=batch, shuffle=False,
                        drop_last=True, num_workers=workers)
    rec = Losses()
    model.fit(loader, epochs=epochs, verbose=0, callbacks=[rec])
    per_epoch = len(rec.values) // epochs
    check(per_epoch >= 1 and len(rec.values) == epochs * per_epoch,
          f"fit ran {len(rec.values)} steps over {epochs} epochs")
    check(all(math.isfinite(v) for v in rec.values),
          f"fit loss not finite: {rec.values}")
    first = sum(rec.values[:per_epoch]) / per_epoch
    last = sum(rec.values[-per_epoch:]) / per_epoch
    check(last < first, f"fit loss did not fall: {rec.values}")
    say("fit", f"resnet{depth}({classes}) {image}x{image} B={batch} "
        f"amp={amp} DataLoader(num_workers={workers}) + prefetcher: "
        f"{len(rec.values)} steps, epoch-mean loss {first:.4f} -> "
        f"{last:.4f}")
    return {"steps": len(rec.values),
            "losses": [round(v, 4) for v in rec.values]}


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------
def phase_serve(dims=GPT_DIMS, prompt_lens=(5, 100, 130, 200), max_new=6,
                slots=4):
    """GPT -> PagedGenerationEngine -> ServingServer on an ephemeral
    localhost port in this process; greedy /v1/generate responses (one
    streamed) must equal the sequential in-process reference."""
    import http.client
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import serving
    from paddle_tpu.models import GPT, GPTConfig
    from paddle_tpu.ops import pallas
    cfg = GPTConfig(**dims)
    paddle.seed(0)
    net = GPT(cfg)
    net.eval()
    rng = np.random.RandomState(0)
    prompts = [rng.randint(0, cfg.vocab_size, (n,)).astype(np.int32)
               for n in prompt_lens]
    before = pallas.selections()
    eng = serving.PagedGenerationEngine(net, serving.GenerationEngineConfig(
        max_slots=slots, max_length=cfg.max_seq_len,
        max_new_tokens=max_new, name="smoke"))
    try:
        with serving.ServingServer(eng, port=0) as srv:
            conn = http.client.HTTPConnection(srv.host, srv.port,
                                              timeout=600)
            got = []
            for i, p in enumerate(prompts):
                stream = i == len(prompts) - 1
                conn.request(
                    "POST", "/v1/generate",
                    json.dumps({"prompt_ids": p.tolist(),
                                "max_new_tokens": max_new,
                                "stream": stream}),
                    {"Content-Type": "application/json"})
                r = conn.getresponse()
                body = r.read().decode()
                check(r.status == 200, f"/v1/generate -> {r.status}: {body}")
                if stream:
                    events = [json.loads(ln[6:]) for ln in body.split("\n")
                              if ln.startswith("data: ")]
                    toks = [e["token"] for e in events if "token" in e]
                    final = [e for e in events if e.get("done")]
                    check(final and final[0]["tokens"] == toks,
                          f"stream events disagree with the final: {body}")
                else:
                    toks = json.loads(body)["tokens"]
                got.append(toks)
            conn.request("GET", "/healthz")
            r = conn.getresponse()
            health = json.loads(r.read())
            check(r.status == 200 and health.get("decode_slots") == slots,
                  f"/healthz -> {r.status} {health}")
            conn.close()
        for p, toks in zip(prompts, got):
            ref = eng.session.generate([p], max_new_tokens=max_new)[0]
            check(len(toks) == max_new
                  and all(0 <= t < cfg.vocab_size for t in toks),
                  f"prompt len {len(p)}: bad tokens {toks}")
            check(toks == ref.tolist(),
                  f"prompt len {len(p)}: served {toks} != reference "
                  f"{ref.tolist()}")
    finally:
        eng.close()
    check(eng.pool.used == 0,
          f"block pool not drained: {eng.pool.used} blocks still held")
    after = pallas.selections()
    ran = {k: v - before.get(k, 0) for k, v in after.items()
           if v != before.get(k, 0)}
    check(not any(k.endswith(".interpret") for k in ran),
          f"a served program holds an interpreted kernel: {ran}")
    say("serve", f"GPT L{cfg.num_layers}/D{cfg.hidden_size} paged engine "
        f"over HTTP: {len(prompts)} requests (prompt lens "
        f"{list(prompt_lens)}, last streamed) == in-process reference; "
        f"/healthz ok; pool drained; attention selections {ran}")
    return {"requests": len(prompts), "selections": ran}


# ---------------------------------------------------------------------------
# multichip
# ---------------------------------------------------------------------------
def phase_multichip(dims=GPT_DIMS, batch=16, microbatches=4,
                    dtype="bfloat16", remat="ctx",
                    meshes=(({"dp": 2, "mp": 2}, "F-then-B"),
                            ({"pp": 2, "mp": 2}, "1F1B"))):
    """The same width over four devices: step-0 loss must equal the
    one-chip step on the same seed and batch within the dryrun's 5e-3,
    and every device must hold parameter shards and live bytes."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from paddle_tpu.distributed.topology import build_mesh
    from paddle_tpu.models import GPTConfig
    from paddle_tpu.models.gpt_spmd import build_spmd_train_step
    devices = jax.devices()
    n = int(np.prod(list(meshes[0][0].values())))
    if len(devices) < n:
        say("multichip", f"skipped ({len(devices)} device)")
        return {"skipped": f"{len(devices)} device"}
    cfg = GPTConfig(**dims)
    ids, labels = _gpt_batch(cfg.vocab_size, batch, cfg.max_seq_len)

    def one(dims_, schedule, devs):
        mesh = build_mesh(dims_, devices=devs)
        step, init_fn = build_spmd_train_step(
            cfg, mesh, num_microbatches=microbatches if "pp" in dims_
            else 1, compute_dtype=jnp.dtype(dtype), remat_policy=remat,
            schedule_mode=schedule)
        params, opt_state = init_fn(seed=0)
        bspec = NamedSharding(mesh, P("dp" if mesh.shape.get("dp", 1) > 1
                                      else None))
        bi, bl = (jax.device_put(np.asarray(a), bspec)
                  for a in (ids, labels))
        n_mosaic = step.lower(params, opt_state, bi, bl).as_text() \
            .count("tpu_custom_call")
        loss, params, opt_state = step(params, opt_state, bi, bl)
        loss = float(loss)
        held = [len(leaf.sharding.device_set)
                for leaf in jax.tree.leaves(params)]
        live = [d.memory_stats()["bytes_in_use"] for d in devs] \
            if devs[0].platform == "tpu" else None
        return loss, n_mosaic, held, live

    ref_loss, _, _, _ = one({"dp": 1}, "F-then-B", devices[:1])
    check(math.isfinite(ref_loss), f"one-chip loss {ref_loss}")
    out = {"one_chip_loss": round(ref_loss, 5), "meshes": []}
    for dims_, schedule in meshes:
        loss, n_mosaic, held, live = one(dims_, schedule, devices[:n])
        check(abs(loss - ref_loss) < 5e-3 * max(1.0, abs(ref_loss)),
              f"{dims_} {schedule}: loss {loss} != one-chip {ref_loss}")
        check(all(h == n for h in held),
              f"{dims_}: a parameter sits on {min(held)} of {n} devices")
        check(live is None or all(b > 0 for b in live),
              f"{dims_}: a device holds no live bytes: {live}")
        check(n_mosaic > 0 or devices[0].platform != "tpu",
              f"{dims_}: the lowered step holds no Mosaic call")
        say("multichip", f"{dims_} {schedule}: step-0 loss {loss:.5f} vs "
            f"one-chip {ref_loss:.5f} (|d|={abs(loss - ref_loss):.1e}); "
            f"{n_mosaic} Mosaic calls; every parameter on {n} devices; "
            f"live bytes/device {live}")
        out["meshes"].append({"dims": dims_, "schedule": schedule,
                              "loss": round(loss, 5),
                              "mosaic_calls": n_mosaic})
    return out


# ---------------------------------------------------------------------------
def main():
    t_start = time.perf_counter()
    meter = CompileMeter()
    device = phase_device()
    from paddle_tpu.utils import compile_cache
    report = {"ok": False, "device": device, "phases": {},
              "compile_cache": {"dir": compile_cache.cache_dir(),
                                "entries_before":
                                    compile_cache.entry_count()}}
    meter.take()
    for name, fn in (("kernels", phase_kernels), ("train", phase_train),
                     ("fit", phase_fit), ("serve", phase_serve),
                     ("multichip", phase_multichip)):
        t0 = time.perf_counter()
        result = fn()
        setup = meter.take()
        setup["wall_s"] = round(time.perf_counter() - t0, 1)
        say(name, f"{'SKIPPED' if 'skipped' in result else 'PASS'} "
            f"platform={device['platform']} wall "
            f"{setup['wall_s']}s, compile {setup['compile_s']}s "
            f"(persistent cache: {setup['cache_hits']} hits, "
            f"{setup['cache_misses']} misses)")
        report["phases"][name] = {**setup, **result}
    report["compile_cache"]["entries_after"] = compile_cache.entry_count()
    report["wall_s"] = round(time.perf_counter() - t_start, 1)
    report["ok"] = True
    report["claim"] = None
    os.makedirs("chiprun_out", exist_ok=True)
    with open(os.path.join("chiprun_out", "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1)
    say("report", json.dumps(report))
    # the result: these keys and no other — whoever runs the script
    # parses this line alone; everything more is in the report above
    print(json.dumps({"ok": True, "device": device}), flush=True)


if __name__ == "__main__":
    sys.exit(main())
