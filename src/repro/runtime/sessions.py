"""Blinding-session pool: pre-generated (r, u) factor sets, N deep.

PR 1's serving loop double-buffered blinding sessions: after dispatching
batch k it enqueued factor generation for batch k+1 — exactly one session
of slack. Under bursty load that is not enough: two back-to-back batches
drain the buffer and the second one pays the ``r @ W_q`` field matmuls on
the request path again.

``SessionPool`` generalizes the double-buffer into an N-deep ring:

- **keys**: session keys are ``fold_in(root, counter)`` with a fresh
  64-bit entropy root per pool (same construction and rationale as the
  legacy server — a colliding root would reuse one-time pads across
  replicas).
- **refill**: a daemon thread keeps ``depth`` sessions prefetched into the
  executor's ``BlindedLayerCache`` (whose ``max_prefetched`` is raised to
  match). JAX dispatch is async, so the refill thread mostly *enqueues*
  device work that overlaps the batcher thread's inference. A prefetched
  factor set carries everything the session's offload needs: (r, u), the
  Freivalds fold vectors under a verification policy, and — when the
  executor runs a multi-device plane — the PER-SHARD fold vectors
  (core/precompute.py ``shards``), so shard-local verification material
  is off the request path too.
- **reuse guard**: every key handed out is remembered (as bytes) and
  re-issue raises — the one-time-pad argument (DESIGN.md §3) dies the
  moment a session is used twice. ``stats()`` exposes
  consumed/refilled/misses/reuse-checked counters for EngineStats.
- **fault containment**: a prefetch that raises increments
  ``refill_errors`` and the loop keeps going — ``acquire`` falls back to
  synchronous factors for that session. ``refill_fault`` is the chaos
  harness's injection point (runtime/chaos.py): a callable run before
  each prefetch, so a drill can script exactly this failure mode.
- **tracing**: with a ``tracer`` (core/tracing.py) every prefetch is one
  ``factors.prefetch`` root span (``counter``, ``n_caches``; a failed one
  carries ``error``, the exception's class name), and ``stats()`` sums
  the caches' ``factors_taken`` / ``factors_sync``: the sets a batch took,
  and those it had to make itself while the request waited.

The pool is executor-agnostic: before the first batch builds the layer
cache, ``prepare`` is a no-op and ``acquire`` simply hands out fresh keys
(factors are then computed on the request path once, as in the seed).
"""
from __future__ import annotations

import os
import threading
from typing import Callable, Dict, Optional, Set

import jax
import numpy as np

from repro.core import tracing


class SessionReuseError(RuntimeError):
    """A blinding session key was issued twice — one-time pad violation."""


class SlotReuseError(RuntimeError):
    """A (session, token) factor slot was issued twice — each decode step's
    pads are one-time material exactly like a forward session's
    (DESIGN.md §16): re-issuing token t would offload two different
    activation vectors under the same r."""


def fresh_root(seed: Optional[int] = None) -> jax.Array:
    """64 entropy bits via two 32-bit words (PRNGKey seeds are C-long)."""
    if seed is not None:
        return jax.random.fold_in(jax.random.PRNGKey(seed & 0xFFFFFFFF),
                                  (seed >> 32) & 0xFFFFFFFF)
    w0, w1 = np.frombuffer(os.urandom(8), np.uint32)
    return jax.random.fold_in(jax.random.PRNGKey(int(w0)), int(w1))


class SessionPool:
    """N-deep pre-generated blinding-session ring for one executor."""

    def __init__(self, executor=None, *, depth: int = 4,
                 root: Optional[jax.Array] = None,
                 background: bool = True,
                 refill_fault: Optional[Callable[[int], None]] = None,
                 tracer: Optional[tracing.Tracer] = None):
        assert depth >= 1, depth
        self.executor = executor
        self.depth = depth
        self.tracer = tracer
        # chaos hook: called with the session counter before each prefetch;
        # raising makes that refill fail exactly like a real one would
        self.refill_fault = refill_fault
        self._root = root if root is not None else fresh_root()
        self._next = 0                     # next counter to prefetch
        self._head = 0                     # next counter to hand out
        self._issued: Set[bytes] = set()
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._closed = False
        # telemetry
        self.consumed = 0
        self.refilled = 0
        self.misses = 0                    # acquired with factors not ready
        self.refill_errors = 0
        self.reuse_checked = 0
        cache = self._cache()
        if cache is not None:
            cache.max_prefetched = max(depth, cache.max_prefetched)
        self._thread: Optional[threading.Thread] = None
        if background:
            self._thread = threading.Thread(
                target=self._refill_loop, name="session-pool-refill",
                daemon=True)
            self._thread.start()

    # -- internals ---------------------------------------------------------
    def _cache(self):
        return getattr(self.executor, "cache", None) if self.executor else None

    def _caches(self):
        """Every layer cache the executor has built — keyed by
        (plan digest, batch shape) since PR 4, so a re-planned executor
        never aliases another plan's prefetch ring.

        The executor swaps ``cache`` per input shape ((model, shape)
        buckets each get their own), so prefetching only into the current
        one would thrash under mixed-shape traffic: every shape switch
        would miss and pay the factor matmuls on the hot path. Prefetching
        each session into all known shape caches costs depth x n_shapes
        factor sets (FIFO-evicted, bounded by max_prefetched) and keeps
        every bucket hitting."""
        if self.executor is None:
            return []
        # snapshot the attribute once: the executor rebinds _caches
        # copy-on-write (origami.py), so the dict we iterate never mutates
        by_shape = getattr(self.executor, "_caches", {})
        caches = {id(c): c for c in by_shape.values() if c is not None}
        cur = self._cache()
        if cur is not None:
            caches.setdefault(id(cur), cur)
        return list(caches.values())

    def _key_for(self, counter: int) -> jax.Array:
        return jax.random.fold_in(self._root, counter)

    def _prefetch(self, counter: int) -> bool:
        """Generate factors for one future session. False if no cache yet."""
        if self.refill_fault is not None:
            self.refill_fault(counter)
        caches = self._caches()
        for cache in caches:
            cache.max_prefetched = max(self.depth + 1, cache.max_prefetched)
            cache.prefetch(self._key_for(counter))
        return bool(caches)

    def _refill(self, counter: int) -> None:
        """One prefetch with its containment: a failure is counted (and
        traced), never raised — a dead refill thread would silently put
        every factor matmul back on the hot path; ``acquire`` falls back
        to synchronous factors for this session."""
        tr = self.tracer
        sp = None
        if tr is not None and tr.enabled:
            # a root span on the refill thread: it has no ambient context
            sp = tr.start_span("factors.prefetch", "session",
                               counter=counter, n_caches=len(self._caches()))
        try:
            ok = self._prefetch(counter)
        except Exception as e:  # noqa: BLE001 — see the docstring
            with self._lock:
                self.refill_errors += 1
            if sp is not None:
                tr.end(sp, error=type(e).__name__)
            return
        if sp is not None:
            tr.end(sp)
        if ok:
            with self._lock:
                self.refilled += 1

    def _refill_loop(self) -> None:
        while True:
            with self._cv:
                while not self._closed and (
                        self._next - self._head >= self.depth
                        or self._cache() is None):
                    # before the first batch builds the layer cache there is
                    # nothing to prefetch — poll instead of burning counters
                    self._cv.wait(
                        timeout=0.05 if self._cache() is None else None)
                if self._closed:
                    return
                counter = self._next
                self._next += 1
            self._refill(counter)

    # -- public API --------------------------------------------------------
    def acquire(self) -> jax.Array:
        """Pop the next never-before-issued session key.

        The key's factors are prefetched whenever the executor's layer
        cache exists; a miss (factors not ready) is counted, not fatal —
        the executor computes them synchronously on first use.
        """
        with self._cv:
            counter = self._head
            self._head += 1
            if self._head > self._next:     # outran the refill thread
                self._next = self._head
            key = self._key_for(counter)
            kb = np.asarray(key).tobytes()
            self.reuse_checked += 1
            if kb in self._issued:
                raise SessionReuseError(
                    f"blinding session {counter} issued twice")
            self._issued.add(kb)
            self.consumed += 1
            cache = self._cache()
            if cache is None or not cache.prefetched(key):
                self.misses += 1
            self._cv.notify_all()           # wake refill to top the pool up
        return key

    def prime(self) -> None:
        """Synchronously top the pool up (e.g. right after the first batch
        built the layer cache, or when running without the thread)."""
        with self._lock:
            start, self._next = self._next, max(self._next,
                                                self._head + self.depth)
            stop = self._next
        for c in range(start, stop):
            self._refill(c)

    def ready(self) -> int:
        """How many handed-out-next sessions have factors prefetched."""
        cache = self._cache()
        if cache is None:
            return 0
        with self._lock:
            head, nxt = self._head, self._next
        return sum(cache.prefetched(self._key_for(c))
                   for c in range(head, nxt))

    def stats(self) -> Dict[str, int]:
        caches = self._caches()
        with self._lock:
            return {"consumed": self.consumed, "refilled": self.refilled,
                    "misses": self.misses, "reuse_checked": self.reuse_checked,
                    "refill_errors": self.refill_errors,
                    "depth": self.depth, "pending": self._next - self._head,
                    "factors_taken": sum(getattr(c, "factors_taken", 0)
                                         for c in caches),
                    "factors_sync": sum(getattr(c, "factors_sync", 0)
                                        for c in caches)}

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=2.0)

    def acquire_stream(self, cache, *, lo: int = 0, depth: int = 8,
                       background: bool = True):
        """Pop the next never-reused session key AND bind a per-token
        factor stream to it: ``(key, TokenSlotRing | None)``.

        ``cache`` is the executor's decode-walk BlindedLayerCache
        (core/origami.py ``decode_cache``) — per-session material, so it
        is NOT part of the pool's forward prefetch ring; each generate
        stream gets its own ring whose slots are keyed by token index.
        ``None`` ring when the decode plan has nothing to blind."""
        key = self.acquire()
        if cache is None:
            return key, None
        return key, TokenSlotRing(cache, key, lo=lo, depth=depth,
                                  background=background,
                                  refill_fault=self.refill_fault)


class TokenSlotRing:
    """Streaming per-token blinding/fold slots for ONE decode session.

    The SessionPool's ring is N sessions deep — a fixed set of
    (session, layer, step=0) factor sets for single-shot traces. Decode
    needs an UNBOUNDED stream instead: every generated token consumes the
    (session, layer, token) factor set of every offloaded op in the scan
    segment. The ring keeps ``depth`` future token slots prefetched
    through ``BlindedLayerCache.session_factors(key, step=token)`` — the
    token index rides the factor keying's existing ``step`` slot, which is
    exactly the stream the jitted token step derives live (ctx.step = the
    traced position), so ring-fed and live traces are bit-identical.

    - **reuse guard**: ``take(token)`` remembers every token index issued
      and raises SlotReuseError on re-issue — one-time pads per
      (session, token, layer).
    - **refill**: a daemon thread tops the ring up ahead of the consumer;
      outrunning it is a counted miss (``take`` falls back to synchronous
      factor generation), never an error.
    - **fault containment**: a failing refill increments
      ``refill_errors`` and the thread keeps going; ``refill_fault`` is
      the chaos hook (called with the token index), as in SessionPool.
    """

    def __init__(self, cache, session_key, *, lo: int = 0, depth: int = 8,
                 background: bool = True,
                 refill_fault: Optional[Callable[[int], None]] = None):
        assert depth >= 1, depth
        self.cache = cache
        self.session_key = session_key
        self.depth = depth
        self.refill_fault = refill_fault
        self._issued: Set[int] = set()
        self._head = lo                    # lowest token not yet taken
        self._next = lo                    # next token to prefetch
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        self._closed = False
        self.consumed = 0
        self.refilled = 0
        self.misses = 0
        self.refill_errors = 0
        # the ring's slots must not FIFO-evict each other before they are
        # taken; leave slack for a take that jumps the head forward
        cache.max_prefetched = max(depth + 2, cache.max_prefetched)
        self._thread: Optional[threading.Thread] = None
        if background:
            self._thread = threading.Thread(
                target=self._refill_loop, name="token-slot-refill",
                daemon=True)
            self._thread.start()

    def _refill_loop(self) -> None:
        while True:
            with self._cv:
                while not self._closed and (
                        self._next - self._head >= self.depth):
                    self._cv.wait()
                if self._closed:
                    return
                token = self._next
                self._next += 1
            try:
                if self.refill_fault is not None:
                    self.refill_fault(token)
                self.cache.prefetch(self.session_key, step=token)
            except Exception:  # noqa: BLE001 — keep the stream alive; the
                # consumer falls back to synchronous factors for this token
                with self._lock:
                    self.refill_errors += 1
                continue
            with self._lock:
                self.refilled += 1

    # -- public API --------------------------------------------------------
    def take(self, token: int):
        """Factor set for decode step ``token`` — prefetched if the ring
        kept up, synchronously generated otherwise (counted miss). Raises
        SlotReuseError if this (session, token) was ever issued before."""
        token = int(token)
        with self._cv:
            if self._closed:
                raise RuntimeError("token-slot ring closed")
            if token in self._issued:
                raise SlotReuseError(
                    f"token slot {token} issued twice for this session")
            self._issued.add(token)
            self.consumed += 1
            if token >= self._head:
                self._head = token + 1
            if self._head > self._next:    # consumer outran the refill
                self._next = self._head
            if not self.cache.prefetched(self.session_key, step=token):
                self.misses += 1
            self._cv.notify_all()          # wake refill to top the ring up
        return self.cache.take(self.session_key, step=token)

    def ready(self) -> int:
        """How many not-yet-taken upcoming slots are prefetched."""
        with self._lock:
            head, nxt = self._head, self._next
        return sum(self.cache.prefetched(self.session_key, step=t)
                   for t in range(head, nxt))

    def stats(self) -> Dict[str, int]:
        with self._lock:
            return {"consumed": self.consumed, "refilled": self.refilled,
                    "misses": self.misses,
                    "refill_errors": self.refill_errors,
                    "depth": self.depth,
                    "pending": self._next - self._head}

    def close(self) -> None:
        with self._cv:
            self._closed = True
            self._cv.notify_all()
        if self._thread is not None:
            self._thread.join(timeout=2.0)
