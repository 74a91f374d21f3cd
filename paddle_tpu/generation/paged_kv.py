"""Paged KV-cache: block-pool serving memory for autoregressive decode.

The PR 6 fixed-capacity cache is correct but memory-naive: every decode
slot owns a dedicated ``(capacity, H, D)`` k/v buffer whether the
request uses 10 tokens or 2000, so concurrent streams per HBM budget
are bounded by the WORST CASE, not the workload.  This module holds KV
memory the way vLLM's PagedAttention does, adapted to fixed-shape XLA
executables:

- **one arena per layer** — pre-allocated ``(num_blocks, block_size,
  H, D)`` k/v buffers shared by every request;
- **per-request block tables** — ``(B, max_blocks)`` int32 arrays of
  arena block indices (``-1`` = unallocated).  Tables are DATA, not
  shape: the compiled prefill/decode steps take them as inputs, so the
  executable population stays bounded by the pow2 prompt buckets
  exactly as before — a block never enters a compile key;
- **gather-based attention** — each step scatters the new tokens' k/v
  into the arenas at table-mapped ``(block, offset)`` slots and
  gathers a per-row dense ``(B, max_blocks*block_size, H, D)`` view
  for the same masked attention math the contiguous cache ran.  With
  ``block_size`` dividing ``max_length`` the view capacity equals the
  contiguous capacity, so paged greedy decode is **bit-exact** against
  the PR 6 path (the paged gate pins it);
- **refcounted alloc/free + copy-on-write** — :class:`BlockPool` is
  the host-side allocator: blocks are refcounted so the prefix cache
  (``prefix_cache.py``) and any number of requests can share filled
  immutable blocks, and a sharer that must append into a partially
  filled shared block copies it first (``GenerationEngine`` drives the
  device copy through :meth:`PagedGenerationSession.copy_blocks`);
- **int8 KV** (``kv_dtype="int8"``) — arenas stored as int8 with
  per-token-per-head scales (the PR 10 per-channel quantization
  surface, in-kernel: ``quantization.quantize_int8_jnp``), dequantized
  inside the attention executable: ~3.6x less HBM per block (the two
  f32 scale planes ride along with the int8 payload) at a pinned
  top-1/bitstream-tolerance gate.

Write validity is encoded in the indices themselves: a write outside
``[starts, limits)`` or into an unallocated table entry gets its block
index mapped to ``num_blocks`` — out of bounds — and XLA's
``mode="drop"`` scatter discards it (NB: ``-1`` would WRAP python-style
and corrupt the last block; the tests pin the drop marker).  Reads
clip ``-1`` entries to block 0; the causal-against-capacity mask
(``kv_cache.attention_mask``) already excludes every slot past a row's
live length, and masked slots contribute exactly-zero softmax weight,
so foreign garbage in unallocated entries never enters the math.

Allocation failures are a first-class serving event: the pool raises
:class:`BlockPoolExhausted` (deterministically injectable via the
``kv.block_alloc`` chaos site) and the engine sheds the request with a
typed ``RequestRejected(reason="kv_blocks")`` instead of corrupting a
live batch.
"""
from __future__ import annotations

from collections import deque
from typing import List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax
import jax.numpy as jnp

from .kv_cache import KVCache, attention_mask
from .sampling import sample as _sample
from .session import GenerationSession

__all__ = ["KVArena", "KVArenaQ", "PagedKV", "BlockPool",
           "BlockPoolExhausted", "PagedGenerationSession",
           "init_arenas", "write_paged", "paged_view",
           "blocks_for_tokens"]


class KVArena(NamedTuple):
    """One layer's float32 paged k/v storage:
    ``(num_blocks, block_size, H, D)`` each."""

    k: jnp.ndarray
    v: jnp.ndarray


class KVArenaQ(NamedTuple):
    """One layer's int8 paged k/v storage plus per-token-per-head
    dequantization scales ``(num_blocks, block_size, H)``."""

    k: jnp.ndarray
    v: jnp.ndarray
    k_scale: jnp.ndarray
    v_scale: jnp.ndarray


class PagedKV(NamedTuple):
    """The per-layer cache the model's attention sees on the paged
    path: one layer's arena plus the (shared) block table and per-row
    absolute write limits.  ``table``/``limits`` are step inputs the
    engine refreshes every call — packing them per layer inside the
    traced step costs nothing and keeps the model's
    ``forward(ids, caches, positions)`` contract unchanged."""

    arena: "KVArena | KVArenaQ"
    table: jnp.ndarray          # (B, max_blocks) int32, -1 = unallocated
    limits: jnp.ndarray         # (B,) int32: writes allowed at [starts, limits)


def blocks_for_tokens(tokens: int, block_size: int) -> int:
    """Blocks needed to hold ``tokens`` cache slots."""
    return -(-int(tokens) // int(block_size))


def init_arenas(num_layers: int, num_blocks: int, block_size: int,
                num_heads: int, head_dim: int,
                quantized: bool = False) -> Tuple:
    """Per-layer tuple of zeroed arenas (the engine-level KV store)."""
    shape = (int(num_blocks), int(block_size), int(num_heads),
             int(head_dim))
    sshape = shape[:3]
    out = []
    for _ in range(int(num_layers)):
        if quantized:
            out.append(KVArenaQ(jnp.zeros(shape, jnp.int8),
                                jnp.zeros(shape, jnp.int8),
                                jnp.zeros(sshape, jnp.float32),
                                jnp.zeros(sshape, jnp.float32)))
        else:
            out.append(KVArena(jnp.zeros(shape, jnp.float32),
                               jnp.zeros(shape, jnp.float32)))
    return tuple(out)


def _write_indices(cache: PagedKV, T: int, starts: jnp.ndarray):
    """Flattened ``(block, offset)`` scatter indices for a ``(B, T)``
    token window written at per-row ``starts``, with every invalid
    write (past ``limits`` or into an unallocated table entry) mapped
    to the out-of-bounds drop marker ``num_blocks``."""
    arena = cache.arena
    N, bs = arena.k.shape[0], arena.k.shape[1]
    M = cache.table.shape[1]
    pos = starts.astype(jnp.int32)[:, None] \
        + jnp.arange(T, dtype=jnp.int32)[None, :]            # (B, T)
    bi = jnp.clip(pos // bs, 0, M - 1)
    blk = jnp.take_along_axis(cache.table, bi, axis=1)       # (B, T)
    valid = (pos < cache.limits.astype(jnp.int32)[:, None]) & (blk >= 0)
    blk = jnp.where(valid, blk, N)       # out of bounds -> mode="drop"
    return blk.reshape(-1), (pos % bs).reshape(-1)


def write_paged(cache: PagedKV, k_new: jnp.ndarray, v_new: jnp.ndarray,
                starts: jnp.ndarray) -> PagedKV:
    """Functional paged-cache update: scatter ``k_new``/``v_new``
    ``(B, T, H, D)`` into the arena at table-mapped slots (int8 arenas
    quantize per token-head on the way in).  Same-structure-out, so
    the whole step stays AOT-stable."""
    arena = cache.arena
    B, T, H, D = k_new.shape
    blk, off = _write_indices(cache, T, starts)
    if isinstance(arena, KVArenaQ):
        from ..quantization import quantize_int8_jnp
        kq, ks = quantize_int8_jnp(k_new, axis=-1)
        vq, vs = quantize_int8_jnp(v_new, axis=-1)
        new = KVArenaQ(
            arena.k.at[blk, off].set(kq.reshape(B * T, H, D),
                                     mode="drop"),
            arena.v.at[blk, off].set(vq.reshape(B * T, H, D),
                                     mode="drop"),
            arena.k_scale.at[blk, off].set(ks.reshape(B * T, H),
                                           mode="drop"),
            arena.v_scale.at[blk, off].set(vs.reshape(B * T, H),
                                           mode="drop"))
    else:
        new = KVArena(
            arena.k.at[blk, off].set(
                k_new.astype(arena.k.dtype).reshape(B * T, H, D),
                mode="drop"),
            arena.v.at[blk, off].set(
                v_new.astype(arena.v.dtype).reshape(B * T, H, D),
                mode="drop"))
    return PagedKV(new, cache.table, cache.limits)


def paged_view(cache: PagedKV) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Dense per-row ``(B, max_blocks*block_size, H, D)`` float32 k/v
    views gathered through the block table (dequantized in-kernel for
    int8 arenas).  View position j == logical cache position j, so the
    standard causal-against-capacity mask applies unchanged;
    unallocated entries clip to block 0 and are always masked."""
    arena = cache.arena
    N, bs, H, D = arena.k.shape
    B, M = cache.table.shape
    idx = jnp.clip(cache.table, 0, N - 1)                    # (B, M)
    k = arena.k[idx].reshape(B, M * bs, H, D)
    v = arena.v[idx].reshape(B, M * bs, H, D)
    if isinstance(arena, KVArenaQ):
        from ..quantization import dequantize_int8_jnp
        k = dequantize_int8_jnp(
            k, arena.k_scale[idx].reshape(B, M * bs, H), axis=-1)
        v = dequantize_int8_jnp(
            v, arena.v_scale[idx].reshape(B, M * bs, H), axis=-1)
    return k, v


def _host_sharding():
    """Sharding that places an array in **pinned host memory** (TPU
    offload — ``core.place.pinned_host_kind``, which raises on a TPU
    that lacks the space); None on a host backend without it, where the
    caller keeps plain numpy arrays."""
    dev = jax.devices()[0]
    from ..core.place import pinned_host_kind
    kind = pinned_host_kind(dev)
    return kind and jax.sharding.SingleDeviceSharding(dev, memory_kind=kind)


class BlockPoolExhausted(RuntimeError):
    """The pool cannot satisfy an allocation (or the ``kv.block_alloc``
    chaos site injected exhaustion).  Engines convert this into a typed
    ``RequestRejected(reason="kv_blocks")`` shed — never a corrupted
    batch."""


class BlockPool:
    """Host-side refcounted allocator over the arena's block axis.

    The pool never touches device memory — it hands out integer block
    ids and keeps the refcounts that let the prefix cache and multiple
    requests share filled blocks.  ``<name>.kv.blocks_in_flight`` (the
    admission signal when paging is on) and ``<name>.kv.block_allocs``
    land in the PR 1 metrics registry.
    """

    def __init__(self, num_blocks: int, block_size: int,
                 name: str = "serving"):
        if num_blocks < 1 or block_size < 1:
            raise ValueError("num_blocks and block_size must be >= 1")
        self.num_blocks = int(num_blocks)
        self.block_size = int(block_size)
        # bytes one block occupies across every layer's k+v arenas
        # (engine fills this in once arenas exist; bench/metrics only)
        self.block_bytes = 0
        from ..utils import concurrency as _conc
        self._lock = _conc.Lock(name=f"{name}.kv.pool")
        self._free: deque = deque(range(self.num_blocks))
        self._ref = np.zeros(self.num_blocks, np.int32)
        from ..profiler import metrics as _metrics
        self._g_used = _metrics.gauge(
            f"{name}.kv.blocks_in_flight",
            "allocated KV blocks (live requests + prefix cache) — the "
            "admission signal when paging is on")
        self._c_alloc = _metrics.counter(
            f"{name}.kv.block_allocs", "KV blocks handed out")
        self._c_exhausted = _metrics.counter(
            f"{name}.kv.alloc_exhausted", "allocations refused because "
            "the pool was empty (incl. injected via kv.block_alloc)")

    @property
    def available(self) -> int:
        with self._lock:
            return len(self._free)

    @property
    def used(self) -> int:
        return self.num_blocks - self.available

    def alloc(self, n: int) -> List[int]:
        """Allocate ``n`` blocks at refcount 1, or raise
        :class:`BlockPoolExhausted` (all-or-nothing — a partial grant
        would leak on the error path).  Chaos site ``kv.block_alloc``
        can inject the exhaustion deterministically."""
        n = int(n)
        if n == 0:
            return []
        from ..profiler import flight as _flight
        from ..utils import chaos as _chaos
        if _chaos.active:
            try:
                _chaos.hit("kv.block_alloc", exc=BlockPoolExhausted)
            except BlockPoolExhausted:
                self._c_exhausted.inc()
                if _flight.active:
                    _flight.note("kv", "exhausted", need=n,
                                 injected=True)
                raise
        with self._lock:
            if len(self._free) < n:
                self._c_exhausted.inc()
                if _flight.active:
                    _flight.note("kv", "exhausted", need=n,
                                 free=len(self._free))
                raise BlockPoolExhausted(
                    f"need {n} KV blocks but only {len(self._free)} of "
                    f"{self.num_blocks} are free (shed, don't corrupt)")
            got = [self._free.popleft() for _ in range(n)]
            for b in got:
                self._ref[b] = 1
            self._c_alloc.inc(n)
            self._g_used.set(self.num_blocks - len(self._free))
        return got

    def incref(self, blocks: Sequence[int]):
        """A new holder (request or prefix cache) shares ``blocks``."""
        with self._lock:
            for b in blocks:
                if self._ref[b] <= 0:
                    raise ValueError(f"incref on free block {b}")
                self._ref[b] += 1

    def decref(self, blocks: Sequence[int]) -> int:
        """Drop one hold per block; blocks reaching refcount 0 return
        to the free list.  Returns how many were actually freed."""
        freed = 0
        with self._lock:
            for b in blocks:
                if self._ref[b] <= 0:
                    raise ValueError(f"decref on free block {b}")
                self._ref[b] -= 1
                if self._ref[b] == 0:
                    self._free.append(b)
                    freed += 1
            self._g_used.set(self.num_blocks - len(self._free))
        return freed

    def refcount(self, block: int) -> int:
        with self._lock:
            return int(self._ref[block])


class PagedGenerationSession(GenerationSession):
    """:class:`GenerationSession` over paged arenas instead of per-row
    contiguous caches.

    The AOT discipline is unchanged — ``jit(step).lower().compile()``
    through the shared ExecutableCache, compiles bounded per pow2
    bucket — but the compiled steps take ``(arenas, block_table)``
    instead of per-row buffers, and prefill generalizes to **chunked**
    prefill: ``(starts, feed_lens)`` let a prefix-cache hit feed only
    the uncached prompt suffix at its true offset.  A paged decode
    step IS the chunk step at width 1 (same function, own width key),
    and the speculative **verify** step is the chunk at width
    ``k+1`` sampling at every position (``speculative.py`` holds the
    drafter + acceptance rule).

    ``block_size`` must divide ``max_length`` so the gathered view
    capacity equals the contiguous capacity — that is what makes paged
    greedy decode bit-exact against the PR 6 reference.
    """

    def __init__(self, model, batch_capacity: int = 1,
                 max_length: Optional[int] = None,
                 block_size: int = 16,
                 num_blocks: Optional[int] = None,
                 kv_dtype: str = "float32",
                 prompt_bucket_min: int = 8,
                 name: str = "generation",
                 executable_cache=None):
        super().__init__(model, batch_capacity=batch_capacity,
                         max_length=max_length,
                         prompt_bucket_min=prompt_bucket_min,
                         name=name, executable_cache=executable_cache)
        self.block_size = int(block_size)
        if self.block_size < 1:
            raise ValueError("block_size must be >= 1")
        if self.max_length % self.block_size:
            raise ValueError(
                f"block_size {self.block_size} must divide max_length "
                f"{self.max_length}: the gathered view capacity must "
                "equal the contiguous capacity for bit-parity")
        self.blocks_per_slot = self.max_length // self.block_size
        self.num_blocks = int(num_blocks if num_blocks is not None
                              else self.batch_capacity
                              * self.blocks_per_slot)
        if kv_dtype not in ("float32", "int8"):
            raise ValueError(f"kv_dtype must be 'float32' or 'int8', "
                             f"got {kv_dtype!r}")
        self.kv_dtype = kv_dtype
        self.quantized = kv_dtype == "int8"
        # arena geometry tag folded into every executable-cache key:
        # arenas are pytrees the base key builder skips, so num_blocks
        # and the storage dtype would otherwise be invisible to a
        # SHARED ExecutableCache and two sessions could collide
        self._ptag = (f"{self.num_blocks}x{self.block_size}"
                      f"{'q' if self.quantized else ''}")
        self._chunk_fn = None
        self._verify_step_fn = None
        self._copy_fn = None

    # -- arena construction -------------------------------------------
    def init_arenas(self) -> Tuple:
        """Zeroed per-layer arenas shaped for this session (via the
        model's ``gen_arenas`` hook when it has one)."""
        hook = getattr(self.model, "gen_arenas", None)
        if hook is not None:
            return hook(self.num_blocks, self.block_size,
                        quantized=self.quantized)
        cfg = self.model.cfg
        return init_arenas(cfg.num_layers, self.num_blocks,
                           self.block_size, cfg.num_heads,
                           cfg.hidden_size // cfg.num_heads,
                           quantized=self.quantized)

    def arena_bytes_per_block(self) -> int:
        """Bytes one block costs across every layer's arenas (k+v and,
        when quantized, scales) — the bench's KV-bytes-per-token
        denominator."""
        arenas = getattr(self, "_abpb_probe", None)
        if arenas is None:
            cfg = self.model.cfg
            hd = cfg.hidden_size // cfg.num_heads
            per = self.block_size * cfg.num_heads * hd
            if self.quantized:
                bpb = 2 * per * 1 + 2 * self.block_size * cfg.num_heads * 4
            else:
                bpb = 2 * per * 4
            self._abpb_probe = bpb * cfg.num_layers
        return self._abpb_probe

    def block_spec(self, arenas=None) -> List[List[Tuple[str, Tuple]]]:
        """Per-layer per-field ``(dtype, per-block shape)`` of this
        session's arenas — the geometry contract a ``kv_wire`` chain
        blob must match before its bytes may enter the pool.  Derives
        from live ``arenas`` when given (covers models with a custom
        ``gen_arenas`` hook); otherwise from the model config."""
        if arenas is not None:
            return [[(str(f.dtype), tuple(int(d) for d in f.shape[1:]))
                     for f in layer] for layer in arenas]
        cfg = self.model.cfg
        hd = cfg.hidden_size // cfg.num_heads
        kv = (self.block_size, cfg.num_heads, hd)
        if self.quantized:
            sc = (self.block_size, cfg.num_heads)
            layer = [("int8", kv), ("int8", kv),
                     ("float32", sc), ("float32", sc)]
        else:
            layer = [("float32", kv), ("float32", kv)]
        return [list(layer) for _ in range(cfg.num_layers)]

    def identity_table(self, rows: Optional[int] = None) -> np.ndarray:
        """Block table mapping row i to its own contiguous run of
        blocks — the standalone :meth:`generate` layout (needs
        ``num_blocks >= rows * blocks_per_slot``)."""
        B = int(rows or self.batch_capacity)
        M = self.blocks_per_slot
        if B * M > self.num_blocks:
            raise ValueError(
                f"identity table needs {B * M} blocks but the pool has "
                f"{self.num_blocks}")
        return (np.arange(B, dtype=np.int32)[:, None] * M
                + np.arange(M, dtype=np.int32)[None, :])

    # -- traced steps -------------------------------------------------
    @staticmethod
    def _pack(arenas, table, limits):
        return tuple(PagedKV(a, table, limits) for a in arenas)

    @staticmethod
    def _unpack(caches):
        return tuple(c.arena for c in caches)

    def _make_chunk(self):
        """The ONE paged step: feed a ``(B, T)`` token window at
        per-row ``starts`` writing ``feed_lens`` tokens, sample the
        token after each row's window.  T = prompt bucket -> prefill;
        T = 1 -> decode.  Rows with ``feed_lens == 0`` are inert
        (no writes; their sampled output is garbage the host ignores).
        """
        net = self.model

        def step(params, buffers, arenas, table, ids, starts,
                 feed_lens, keys, temps, tks, tps):
            from ..core import autograd
            from ..core.tensor import Tensor
            limits = starts + feed_lens
            with autograd.no_grad():
                net.load_functional_state(params, buffers)
                caches = PagedGenerationSession._pack(
                    arenas, table, limits)
                logits, new_caches = net.forward(
                    Tensor(ids), caches=caches, positions=starts)
            logits = logits._data
            idx = jnp.clip(feed_lens - 1, 0, ids.shape[1] - 1)
            last = jnp.take_along_axis(
                logits, idx[:, None, None], axis=1)[:, 0]    # (B, V)
            # the sampled token sits at absolute position ``limits``:
            # fold the row key there (decode and the contiguous path
            # fold identically, so streams stay bit-reproducible)
            step_keys = jax.vmap(jax.random.fold_in)(keys, limits)
            tok = _sample(last, step_keys, temps, tks, tps)
            return tok, PagedGenerationSession._unpack(new_caches)
        return step

    def _make_verify(self):
        """Speculative verify: the chunk step sampling at EVERY window
        position — one batched executable accepts a whole draft span.
        Chunk index i of a row fed at position p is the token AT
        ``p + i``; its successor is sampled with the key folded at
        ``p + 1 + i`` — exactly the fold sequential decode would use,
        which is the greedy-equivalence (and sampled-equivalence)
        guarantee."""
        net = self.model

        def step(params, buffers, arenas, table, ids, starts,
                 feed_lens, keys, temps, tks, tps):
            from ..core import autograd
            from ..core.tensor import Tensor
            W = ids.shape[1]
            limits = starts + feed_lens
            with autograd.no_grad():
                net.load_functional_state(params, buffers)
                caches = PagedGenerationSession._pack(
                    arenas, table, limits)
                logits, new_caches = net.forward(
                    Tensor(ids), caches=caches, positions=starts)
            logits = logits._data                      # (B, W, V)
            posmat = starts.astype(jnp.int32)[:, None] + 1 \
                + jnp.arange(W, dtype=jnp.int32)[None, :]
            step_keys = jax.vmap(jax.vmap(jax.random.fold_in,
                                          in_axes=(None, 0)))(keys,
                                                              posmat)
            toks = jax.vmap(_sample, in_axes=(1, 1, None, None, None),
                            out_axes=1)(logits, step_keys, temps, tks,
                                        tps)           # (B, W)
            return toks, PagedGenerationSession._unpack(new_caches)
        return step

    def _make_copy(self):
        """Copy-on-write device helper: arena[dst[i]] = arena[src[i]]
        per layer, every field.  Pairs with src or dst < 0 are inert
        (mapped to the drop marker)."""
        N = self.num_blocks

        def step(arenas, src, dst):
            valid = (src >= 0) & (dst >= 0)
            d = jnp.where(valid, dst, N)
            s = jnp.clip(src, 0, N - 1)
            return tuple(
                type(a)(*[f.at[d].set(f[s], mode="drop") for f in a])
                for a in arenas)
        return step

    # -- step drivers -------------------------------------------------
    def _paged_args(self, arenas, table, ids, starts, feed_lens, keys,
                    temps, tks, tps):
        params, buffers = self._state_snapshot()
        return (params, buffers, arenas,
                jnp.asarray(table, jnp.int32),
                jnp.asarray(ids, jnp.int32),
                jnp.asarray(starts, jnp.int32),
                jnp.asarray(feed_lens, jnp.int32),
                jnp.asarray(keys, jnp.uint32),
                jnp.asarray(temps, jnp.float32),
                jnp.asarray(tks, jnp.int32),
                jnp.asarray(tps, jnp.float32))

    def prefill(self, arenas, table, ids, starts, feed_lens, keys,
                temps, tks, tps, live_rows: Optional[int] = None):
        """Chunked paged prefill: write each row's ``feed_lens`` tokens
        at ``starts`` (a prefix-cache hit passes the cached length),
        sample the next token.  Returns ``(tokens (B,), arenas)``."""
        import time as _time
        if self._chunk_fn is None:
            self._chunk_fn = self._make_chunk()
        args = self._paged_args(arenas, table, ids, starts, feed_lens,
                                keys, temps, tks, tps)
        exe = self._compiled(f"pchunk[{self._ptag}]:{ids.shape[1]}",
                             self._chunk_fn, args)
        t0 = _time.perf_counter_ns()
        tok, arenas = exe(*args)
        tok_h = np.asarray(tok)
        self._observe(self._m_prefill, "prefill", t0)
        n = live_rows if live_rows is not None else \
            int((np.asarray(feed_lens) > 0).sum())
        self._m_tokens.inc(int(n))
        return tok_h, arenas

    def decode(self, arenas, table, tokens, positions, keys, temps,
               tks, tps, live_rows: Optional[int] = None):
        """Paged decode = the chunk step at width 1 (one compile for
        the session lifetime, same as the contiguous decode bound)."""
        import time as _time
        if self._chunk_fn is None:
            self._chunk_fn = self._make_chunk()
        ids = np.asarray(tokens, np.int32).reshape(-1, 1)
        ones = np.ones((ids.shape[0],), np.int32)
        args = self._paged_args(arenas, table, ids, positions, ones,
                                keys, temps, tks, tps)
        exe = self._compiled(f"pchunk[{self._ptag}]:1",
                             self._chunk_fn, args)
        t0 = _time.perf_counter_ns()
        tok, arenas = exe(*args)
        tok_h = np.asarray(tok)
        self._observe(self._m_decode, "decode", t0)
        self._m_tokens.inc(int(live_rows if live_rows is not None
                               else len(tok_h)))
        return tok_h, arenas

    def verify(self, arenas, table, ids, positions, feed_lens, keys,
               temps, tks, tps, live_rows: Optional[int] = None):
        """Speculative verify step: ``ids (B, W)`` = [last_token,
        draft_1..draft_{W-1}] per row; returns ``(tokens (B, W),
        arenas)`` — the sampled successor of every window position.
        One executable per draft width."""
        import time as _time
        if self._verify_step_fn is None:
            self._verify_step_fn = self._make_verify()
        args = self._paged_args(arenas, table, ids, positions,
                                feed_lens, keys, temps, tks, tps)
        exe = self._compiled(f"pverify[{self._ptag}]:{ids.shape[1]}",
                             self._verify_step_fn, args)
        t0 = _time.perf_counter_ns()
        toks, arenas = exe(*args)
        toks_h = np.asarray(toks)
        self._observe(self._m_decode, "decode", t0)
        if live_rows:
            self._m_tokens.inc(int(live_rows))
        return toks_h, arenas

    def copy_blocks(self, arenas, src: Sequence[int],
                    dst: Sequence[int]):
        """Device-side block copies (copy-on-write): fixed-width
        (batch_capacity) src/dst index vectors, inert entries -1 —
        one compile regardless of how many copies a round needs."""
        pairs = list(zip(src, dst))
        if not pairs:
            return arenas
        if self._copy_fn is None:
            self._copy_fn = self._make_copy()
        W = self.batch_capacity
        for chunk in range(0, len(pairs), W):
            batch = pairs[chunk:chunk + W]
            s = np.full((W,), -1, np.int32)
            d = np.full((W,), -1, np.int32)
            for i, (a, b) in enumerate(batch):
                s[i], d[i] = a, b
            args = (arenas, jnp.asarray(s), jnp.asarray(d))
            exe = self._compiled(f"pcopy[{self._ptag}]",
                                 self._copy_fn, args)
            arenas = exe(*args)
        return arenas

    # -- preemption swap (engine-driven) ------------------------------
    def swap_out_blocks(self, arenas, blocks: Sequence[int]):
        """Gather ``blocks``' contents (every layer, every arena
        field — k/v and, when quantized, the scale planes) to HOST
        memory so the engine can free the device blocks for
        higher-priority work.  Pinned host memory (``pinned_host``
        memory kind); plain numpy arrays on a host backend without
        that space.  Blocked until the copies land — the caller decrefs
        the blocks immediately after, so the gather must not race
        their reuse.  Returns an opaque per-layer payload for
        :meth:`swap_in_blocks`."""
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        host = _host_sharding()
        out = []
        for a in arenas:
            fields = []
            for f in a:
                g = f[idx]                       # (n, bs, ...) gather
                if host is not None:
                    g = jax.device_put(g, host)
                    g.block_until_ready()
                else:
                    g = np.asarray(g)            # sync host copy
                fields.append(g)
            out.append(tuple(fields))
        return out

    def swap_in_blocks(self, arenas, blocks: Sequence[int], payload):
        """Restore a :meth:`swap_out_blocks` payload into freshly
        allocated ``blocks``: ``device_put`` + scatter per layer/field.
        Contents are bit-identical to what was swapped out (pure
        copies, no recompute), which is what makes a resumed stream
        bit-exact — the block *ids* may differ, the block-table
        rewrite absorbs that."""
        idx = jnp.asarray(np.asarray(blocks, np.int32))
        new = []
        for a, fields in zip(arenas, payload):
            new.append(type(a)(*[
                f.at[idx].set(jnp.asarray(h))
                for f, h in zip(a, fields)]))
        return tuple(new)

    # -- high-level generate ------------------------------------------
    def generate(self, ids, prompt_lens=None, max_new_tokens: int = 32,
                 do_sample: bool = False, temperature: float = 1.0,
                 top_k: int = 0, top_p: float = 1.0, seed: int = 0,
                 seeds=None, eos_token_id: Optional[int] = None,
                 stream_callback=None, speculative_k: int = 0,
                 spec_ngram: int = 2) -> List[np.ndarray]:
        """Paged twin of :meth:`GenerationSession.generate` (same
        contract, identity block table) plus opt-in speculative
        decoding: ``speculative_k`` drafts per step from the n-gram
        prompt-lookup drafter, committed via one verify call — output
        streams are bit-identical to ``speculative_k=0`` (the
        acceptance rule only ever commits tokens the sequential
        sampler would have produced)."""
        ids_list, lens, batch, keys, temps, tks, tps = \
            self._prep_batch(ids, prompt_lens, do_sample, temperature,
                             top_k, top_p, seed, seeds)
        B_real = len(ids_list)
        B = self.batch_capacity
        feed = np.zeros((B,), np.int32)
        feed[:B_real] = lens

        arenas = self.init_arenas()
        table = self.identity_table()
        tok, arenas = self.prefill(arenas, table, batch,
                                   np.zeros((B,), np.int32), feed,
                                   keys, temps, tks, tps,
                                   live_rows=B_real)
        out: List[List[int]] = [[] for _ in range(B_real)]
        done = [False] * B_real
        positions = feed.copy()         # where the sampled token sits
        max_new = max(int(max_new_tokens), 1)
        last = np.array(tok, np.int32)

        def absorb_one(i, t):
            out[i].append(t)
            if stream_callback is not None:
                stream_callback(i, t)
            if eos_token_id is not None and t == int(eos_token_id):
                done[i] = True
            elif len(out[i]) >= max_new:
                done[i] = True
            elif positions[i] + 1 >= self.max_length:
                done[i] = True          # cache full: hard stop

        for i in range(B_real):
            absorb_one(i, int(tok[i]))

        k_spec = max(int(speculative_k), 0)
        from .speculative import accept_span, draft_row, \
            fill_verify_row
        while not all(done):
            live = sum(1 for d in done if not d)
            if k_spec == 0:
                tok, arenas = self.decode(
                    arenas, table, last, positions, keys, temps, tks,
                    tps, live_rows=live)
                positions = positions + 1
                for i in range(B_real):
                    if not done[i]:
                        last[i] = tok[i]
                        absorb_one(i, int(tok[i]))
                continue
            W = k_spec + 1
            step_ids = np.zeros((B, W), np.int32)
            feed_w = np.zeros((B,), np.int32)
            drafts: List[List[int]] = [[] for _ in range(B)]
            for i in range(B_real):
                if done[i]:
                    continue
                ctx = np.concatenate([ids_list[i],
                                      np.asarray(out[i], np.int32)])
                room = self.max_length - int(positions[i])
                d = draft_row(ctx, k_spec, room, ngram=spec_ngram)
                drafts[i] = d
                fill_verify_row(step_ids, feed_w, i, int(last[i]), d)
            toks, arenas = self.verify(
                arenas, table, step_ids, positions, feed_w, keys,
                temps, tks, tps, live_rows=live)
            for i in range(B_real):
                if done[i]:
                    continue
                span = accept_span(drafts[i], toks[i])
                for t in span:
                    positions[i] = positions[i] + 1
                    last[i] = t
                    absorb_one(i, int(t))
                    if done[i]:
                        break
        return [np.asarray(o, np.int32) for o in out]
