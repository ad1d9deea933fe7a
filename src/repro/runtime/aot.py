"""Compile-once serving: AOT executable cache for the plan interpreter.

The ROADMAP throughput item starts from a measured fact (PR 8's phase
profiler): the engine's 7.7 s time-to-first-batch was almost entirely
``jax.jit`` trace+compile paid on the first request's critical path, per
(trace-kind, plan digest, shape bucket) signature. This module moves that
cost off the request path twice over:

- **in-process**: every executable the OrigamiExecutor runs is compiled
  through ``CompileCache.compile_once`` — an explicitly timed
  ``jax.jit(...).lower(...).compile()`` (never an implicit first-call
  compile), memoized per cache key and serialized by a per-key lock so
  concurrent ``register_model`` / mixed-shape submits compile each
  (plan digest, shape bucket) exactly once.
- **across processes**: with a ``cache_dir``, compiled executables are
  persisted via ``jax.experimental.serialize_executable`` and reloaded on
  the next boot — the first request of a *restarted* server never pays
  compile either.

Cache key (DESIGN.md §15): ``sha256(plan digest, trace kind, input-shape
signature, backend, jax version, code version)``. The plan digest pins
*what* the executable computes (placement IR + weights provenance); the
shape signature pins the padded bucket; backend + jax version pin the
lowering; the code version — a content hash over the repro source that
shapes traced programs — invalidates stale entries when the interpreter
itself changes (a stale executable would silently serve an old program:
fail closed to a fresh compile). A corrupted or stale payload is counted
(``aot.disk_errors``) and falls back to a fresh compile, never to a
failed request.

Counters (MetricsRegistry, §13 names): ``aot.compiles`` /
``aot.disk_hits`` / ``aot.memo_hits`` / ``aot.disk_errors`` /
``aot.stores``; gauges ``aot.compile_seconds`` (total) and
``aot.request_compile_seconds`` (the subset paid on the request path —
zero when registration warmed every bucket, which is what makes
``ttfb_warm_s`` visible in EngineStats).
"""
from __future__ import annotations

import hashlib
import os
import pathlib
import pickle
import tempfile
import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax

from jax.experimental import serialize_executable as _sx

_PAYLOAD_VERSION = 1

# source roots whose content shapes the traced program — a change in any
# of them must invalidate persisted executables (core: plan interpreter +
# blinding math; kernels: the field matmuls; models: the layer algebra)
_CODE_ROOTS = ("core", "kernels", "models")

_code_version_cache: Optional[str] = None
_code_version_lock = threading.Lock()

# src/repro/runtime/aot.py -> the checkout root
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def use_persistent_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting and is
    used as it stands; otherwise the cache lives at the fixed
    ``<checkout>/.jax_cache``, so a later run finds what an earlier one
    compiled. Returns the directory in use."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = str(_CHECKOUT / ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    return path


def code_version() -> str:
    """Content hash over the source that determines traced programs.

    Hashed once per process (sorted walk — deterministic across runs).
    """
    global _code_version_cache
    with _code_version_lock:
        if _code_version_cache is not None:
            return _code_version_cache
        h = hashlib.sha256()
        pkg_root = pathlib.Path(__file__).resolve().parent.parent
        for root in _CODE_ROOTS:
            base = pkg_root / root
            if not base.is_dir():
                continue
            for path in sorted(base.rglob("*.py")):
                h.update(path.relative_to(pkg_root).as_posix().encode())
                h.update(path.read_bytes())
        _code_version_cache = h.hexdigest()[:16]
        return _code_version_cache


def shape_signature(tree: Any) -> str:
    """Stable string signature of a pytree's avals (shape + dtype)."""
    leaves = jax.tree_util.tree_leaves(tree)
    parts = []
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = getattr(leaf, "dtype", type(leaf).__name__)
        parts.append(f"{'x'.join(map(str, shape))}:{dtype}")
    return ";".join(parts)


class CompileCache:
    """Memoized + optionally disk-persistent executable cache.

    One instance per engine (``ServingEngine.aot``), shared by every
    registered executor: the in-process memo deduplicates identical
    (digest, kind, bucket) compiles across executors, the per-key locks
    make concurrent compiles exactly-once, and the counters land in the
    engine's MetricsRegistry.
    """

    def __init__(self, cache_dir: Optional[str] = None,
                 registry=None) -> None:
        self.cache_dir = pathlib.Path(cache_dir) if cache_dir else None
        self.registry = registry
        self._memo: Dict[str, Any] = {}
        self._lock = threading.Lock()
        self._key_locks: Dict[str, threading.Lock] = {}
        # local counters mirror the registry so the cache is usable (and
        # testable) without an engine attached
        self.counters: Dict[str, int] = {
            "compiles": 0, "memo_hits": 0, "disk_hits": 0,
            "disk_errors": 0, "stores": 0}
        self.compile_seconds = 0.0
        self.request_compile_seconds = 0.0
        # registration-time warmups flip this on so compile seconds are
        # attributed to warmup, not the request path (thread-local: the
        # batcher/device threads must never inherit a warmup flag from a
        # concurrent register_model on the main thread)
        self._tls = threading.local()

    # -- warmup attribution ------------------------------------------------
    class _WarmupScope:
        def __init__(self, cache: "CompileCache") -> None:
            self.cache = cache

        def __enter__(self) -> None:
            self.cache._tls.warmup = getattr(
                self.cache._tls, "warmup", 0) + 1

        def __exit__(self, *exc) -> None:
            self.cache._tls.warmup -= 1

    def warmup_scope(self) -> "CompileCache._WarmupScope":
        """Context manager: compiles inside it count as warmup, not
        request-path, in the ``aot.request_compile_seconds`` split."""
        return CompileCache._WarmupScope(self)

    @property
    def in_warmup(self) -> bool:
        return getattr(self._tls, "warmup", 0) > 0

    # -- keys --------------------------------------------------------------
    def entry_key(self, plan_digest: str, kind: str, args: Any) -> str:
        """The §15 cache key: plan digest + trace kind + shape signature +
        backend + jax version + code version, hashed."""
        raw = "|".join((str(plan_digest), str(kind), shape_signature(args),
                        jax.default_backend(), jax.__version__,
                        code_version()))
        return hashlib.sha256(raw.encode()).hexdigest()

    # -- counters ----------------------------------------------------------
    def _bump(self, name: str, n: int = 1) -> None:
        with self._lock:
            self.counters[name] = self.counters.get(name, 0) + n
        if self.registry is not None:
            self.registry.inc(f"aot.{name}", n)

    def _add_seconds(self, dt: float) -> None:
        with self._lock:
            self.compile_seconds += dt
            if not self.in_warmup:
                self.request_compile_seconds += dt
        if self.registry is not None:
            self.registry.gauge("aot.compile_seconds",
                                self.compile_seconds)
            self.registry.gauge("aot.request_compile_seconds",
                                self.request_compile_seconds)

    def stats(self) -> Dict[str, Any]:
        with self._lock:
            out: Dict[str, Any] = dict(self.counters)
            out["compile_seconds"] = round(self.compile_seconds, 6)
            out["request_compile_seconds"] = round(
                self.request_compile_seconds, 6)
            out["persistent"] = self.cache_dir is not None
        return out

    # -- disk layer --------------------------------------------------------
    def _path(self, key: str) -> Optional[pathlib.Path]:
        if self.cache_dir is None:
            return None
        return self.cache_dir / f"{key}.xc"

    def _disk_load(self, key: str) -> Optional[Any]:
        path = self._path(key)
        if path is None or not path.exists():
            return None
        try:
            with open(path, "rb") as fh:
                doc = pickle.load(fh)
            if (doc.get("v") != _PAYLOAD_VERSION
                    or doc.get("jax") != jax.__version__
                    or doc.get("code") != code_version()):
                raise ValueError("stale compile-cache entry")
            compiled = _sx.deserialize_and_load(
                doc["payload"], doc["in_tree"], doc["out_tree"])
            self._bump("disk_hits")
            return compiled
        except Exception:  # noqa: BLE001 — corrupt/stale/incompatible:
            # fail closed to a fresh compile, never to a failed request
            self._bump("disk_errors")
            return None

    def _disk_store(self, key: str, compiled: Any) -> None:
        path = self._path(key)
        if path is None:
            return
        try:
            payload, in_tree, out_tree = _sx.serialize(compiled)
            doc = {"v": _PAYLOAD_VERSION, "jax": jax.__version__,
                   "code": code_version(), "payload": payload,
                   "in_tree": in_tree, "out_tree": out_tree}
            path.parent.mkdir(parents=True, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=str(path.parent),
                                       suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as fh:
                    pickle.dump(doc, fh)
                os.replace(tmp, path)   # atomic: readers never see partials
            finally:
                if os.path.exists(tmp):
                    os.unlink(tmp)
            self._bump("stores")
        except Exception:  # noqa: BLE001 — persistence is an optimization;
            # a full disk or unpicklable tree must not fail serving
            self._bump("disk_errors")

    # -- the one compile path ----------------------------------------------
    def compile_once(self, key: str, build: Callable[[], Any],
                     on_disk_hit: Optional[Callable[[], None]] = None
                     ) -> Tuple[Any, bool]:
        """Return ``(compiled, fresh)`` for ``key`` — memo, then disk,
        then a timed fresh ``build()`` (which must do lower+compile).

        Per-key locking makes concurrent callers exactly-once: the loser
        of the race finds the winner's memo entry. ``on_disk_hit`` runs
        after a successful disk load (the executor uses it to replay
        trace-time telemetry side effects that a deserialized executable
        skips).
        """
        with self._lock:
            compiled = self._memo.get(key)
            if compiled is None:
                klock = self._key_locks.setdefault(key, threading.Lock())
        if compiled is not None:
            self._bump("memo_hits")
            return compiled, False
        with klock:
            with self._lock:
                compiled = self._memo.get(key)
            if compiled is not None:
                self._bump("memo_hits")
                return compiled, False
            compiled = self._disk_load(key)
            if compiled is not None:
                if on_disk_hit is not None:
                    on_disk_hit()
                with self._lock:
                    self._memo[key] = compiled
                return compiled, False
            t0 = time.monotonic()
            compiled = build()
            self._add_seconds(time.monotonic() - t0)
            self._bump("compiles")
            self._disk_store(key, compiled)
            with self._lock:
                self._memo[key] = compiled
            return compiled, True


def bucket_ladder(max_batch: int) -> Tuple[int, ...]:
    """The shape-bucket ladder: powers of two up to (and including)
    ``max_batch`` — 1/2/4/max for the default engine config."""
    out = []
    b = 1
    while b < max_batch:
        out.append(b)
        b *= 2
    out.append(max_batch)
    return tuple(out)


def bucket_for(n: int, max_batch: int) -> int:
    """Smallest ladder bucket holding ``n`` requests (occupancy-driven
    padding: a lone request pads to 1, not to max_batch)."""
    assert 1 <= n <= max_batch, (n, max_batch)
    b = 1
    while b < n:
        b *= 2
    return min(b, max_batch)
