"""Request-scoped span tracing with mandatory privacy redaction.

Origami's pipeline crosses many stages with wildly different costs —
seal -> queue -> batch -> session -> plan step -> shard dispatch -> verify ->
unseal — and the ROADMAP's throughput work needs to *attribute* a request's
latency across them, not guess. ``Tracer`` records a span tree per request:
the engine opens a ``request`` root at submit, every downstream stage
(runtime/serving.py, core/origami.py, core/slalom.py,
parallel/offload_sharding.py) attaches children via the ambient context,
and the whole tree exports as Chrome-trace JSON (chrome://tracing /
Perfetto) or JSONL.

Spans are the host's view. What the device does inside one compiled
program is named by ``DEVICE_PHASES``: each phase's traced code runs under
a ``jax.named_scope`` of that name, which rides into the HLO as op
metadata and so into the profiler's trace at no run-time cost.
``Tracer.clock_anchor`` writes the tracer's own clock into the profiler's
trace, so spans and device operations can be laid on one time axis.

**Telemetry is a threat surface.** In a TEE deployment the trace file
leaves the trust boundary (dashboards, CI artifacts), and Privado-style
attacks reconstruct model internals from input-dependent observables — so
redaction is not a post-processing step here, it is enforced at
*attach time*: a span attribute must be a plain scalar / short string /
small container thereof. Arrays (jax or numpy), bytes, and any object
carrying a buffer are rejected with ``RedactionError`` — blinding factors,
session keys, plaintext activations and raw logits structurally cannot
ride a span. Spans carry shapes, digests, counts and timings only
(DESIGN.md §13 scopes what this does and does not cover: timing itself
still leaks input-dependent control flow, which Origami's pipeline avoids
by construction — per-step work depends on shapes, not values).

Threading model: spans are created/closed on whatever thread runs the
stage; the tracer is lock-protected and parentage is explicit (``parent=``)
or ambient via a contextvar (``activate``). Contextvars do not propagate
into pre-existing worker threads (device slots, refill threads) — stages
that hop threads pass the parent span explicitly, which is also what keeps
a worker thread from paying the tracer lock on its hot path.

Everything is a no-op when no tracer is active: the ambient lookup is one
contextvar read, so instrumented code costs nothing in production serving
(BENCH_trace_overhead.json holds the tracing-ON path under 5%).
"""
from __future__ import annotations

import itertools
import json
import threading
import time
from contextlib import contextmanager
from contextvars import ContextVar
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

_ids = itertools.count(1)          # CPython next() is atomic

# span kinds (the taxonomy DESIGN.md §13 tabulates)
KINDS = ("request", "queue", "batch", "session", "crypto", "infer",
         "step", "shard", "verify", "compile")

# device phases: ``jax.named_scope`` names the program's traced code runs
# under (DESIGN.md §13). A scope is HLO metadata only — it adds no device
# work and changes no output bit — and it reaches only ops traced inside
# it, so every phase below is traced inside some jit.
ENCLAVE_UNSEAL = "enclave.unseal"   # request keystream + MAC (sealing.py)
CLIENT_OPEN = "client.open"         # the client's response open, same body
ENCLAVE_SEAL = "enclave.seal"       # response keystream + MAC
TIER1_PATCHES = "tier1.patches"     # im2col of a blinded conv (slalom.py)
TIER1_BLIND = "tier1.blind"         # absmax scale, quantize + blind-encode
TIER1_MATMUL = "tier1.matmul"       # the device's field / fused limb matmul
TIER1_UNBLIND = "tier1.unblind"     # unblind, dequantize, bias, and the
                                    # enclave's activations and pooling
TIER1_VERIFY = "tier1.verify"       # Freivalds fold check (integrity.py)
TIER2_OPEN = "tier2.open"           # plain plan segments (origami.py)
FACTORS_PAD = "factors.pad"         # session pads r (precompute.py)
FACTORS_UNBLIND = "factors.unblind"  # u = r·W_q
FACTORS_FOLD = "factors.fold"       # fold vectors s and W_q·s
DEVICE_PHASES = (ENCLAVE_UNSEAL, CLIENT_OPEN, ENCLAVE_SEAL, TIER1_PATCHES,
                 TIER1_BLIND, TIER1_MATMUL, TIER1_UNBLIND, TIER1_VERIFY,
                 TIER2_OPEN, FACTORS_PAD, FACTORS_UNBLIND, FACTORS_FOLD)

# the profiler annotation that carries the tracer's clock (clock_anchor)
CLOCK_ANCHOR = "tracer.clock"

_MAX_STR = 512                     # longest attribute string (digests fit)
_MAX_ITEMS = 64                    # longest attribute list/dict


class RedactionError(TypeError):
    """A span attribute carried a disallowed payload (array/bytes/object).

    Raised at attach time — the trace plane fails CLOSED: secret-bearing
    values never reach the span store, let alone an export file."""


def redact(value: Any, _depth: int = 0) -> Any:
    """Validate one attribute value against the allowlist.

    Allowed: None, bool, int, float, str (truncated to ``_MAX_STR``), and
    lists/tuples/dicts of allowed values (bounded). Everything else —
    notably jax/numpy arrays, bytes-likes, and arbitrary objects — raises
    ``RedactionError``. Types are checked *exactly* (no duck-typing): a
    subclass with a buffer would sail through an isinstance check.
    """
    if value is None or type(value) in (bool, int, float):
        return value
    if isinstance(value, str):
        return value if len(value) <= _MAX_STR else value[:_MAX_STR] + "…"
    if isinstance(value, (bytes, bytearray, memoryview)):
        raise RedactionError(
            "span attributes must not carry raw bytes (key material, "
            "ciphertext, array buffers) — attach a digest or a shape")
    if hasattr(value, "__array__") or hasattr(value, "shape"):
        raise RedactionError(
            f"span attributes must not carry arrays ({type(value).__name__})"
            " — blinding factors / activations / logits are secret; attach "
            "the shape tuple or a digest instead")
    if isinstance(value, (list, tuple)):
        if _depth >= 3 or len(value) > _MAX_ITEMS:
            raise RedactionError("span attribute container too large/deep")
        return [redact(v, _depth + 1) for v in value]
    if isinstance(value, dict):
        if _depth >= 3 or len(value) > _MAX_ITEMS:
            raise RedactionError("span attribute container too large/deep")
        return {str(k)[:_MAX_STR]: redact(v, _depth + 1)
                for k, v in value.items()}
    raise RedactionError(
        f"span attribute type {type(value).__name__!r} is not on the "
        "redaction allowlist (scalars, short strings, small containers)")


@dataclass
class Span:
    """One timed stage. ``t0``/``t1`` are perf_counter seconds relative to
    the tracer's epoch; attributes are pre-redacted."""
    trace_id: int
    span_id: int
    parent_id: Optional[int]
    name: str
    kind: str
    t0: float
    t1: Optional[float] = None
    tid: int = 0
    attrs: Dict[str, Any] = field(default_factory=dict)

    @property
    def duration_s(self) -> Optional[float]:
        return None if self.t1 is None else self.t1 - self.t0

    def as_dict(self) -> Dict[str, Any]:
        return {"trace_id": self.trace_id, "span_id": self.span_id,
                "parent_id": self.parent_id, "name": self.name,
                "kind": self.kind, "t0": self.t0, "t1": self.t1,
                "tid": self.tid, "attrs": dict(self.attrs)}


class Tracer:
    """Thread-safe bounded span store with redaction-enforced attributes.

    ``kernel_spans`` is retired and ignored: device time per phase comes
    from the profiler's trace (``DEVICE_PHASES``), not from host spans
    fenced around each kernel. It is still accepted because benchmark
    harnesses of earlier versions pass it.
    """

    MAX_SPANS = 200_000

    def __init__(self, *, enabled: bool = True,
                 kernel_spans: Optional[bool] = None,
                 max_spans: int = MAX_SPANS):
        del kernel_spans
        self.enabled = enabled
        self.max_spans = max_spans
        self.epoch = time.perf_counter()
        self.epoch_unix = time.time()
        self.dropped = 0                  # spans past the bound
        self._spans: List[Span] = []
        self._lock = threading.Lock()

    # -- recording ---------------------------------------------------------
    def start_span(self, name: str, kind: str = "step", *,
                   parent: Optional[Span] = None,
                   trace_id: Optional[int] = None,
                   **attrs: Any) -> Span:
        """Open a span. Parent resolution: explicit ``parent``, else the
        ambient current span (same thread), else a new root (fresh
        trace_id unless given)."""
        if parent is None:
            parent = current_span()
        sid = next(_ids)
        tid = (parent.trace_id if parent is not None
               else (trace_id if trace_id is not None else next(_ids)))
        span = Span(trace_id=tid, span_id=sid,
                    parent_id=parent.span_id if parent else None,
                    name=name, kind=kind,
                    t0=time.perf_counter() - self.epoch,
                    tid=threading.get_ident())
        if attrs:
            self.annotate(span, **attrs)
        with self._lock:
            if len(self._spans) < self.max_spans:
                self._spans.append(span)
            else:
                self.dropped += 1
        return span

    def annotate(self, span: Span, **attrs: Any) -> None:
        """Attach attributes (redaction enforced — raises on violations
        BEFORE anything is stored)."""
        clean = {k: redact(v) for k, v in attrs.items()}
        span.attrs.update(clean)

    def end(self, span: Span, **attrs: Any) -> Span:
        if attrs:
            self.annotate(span, **attrs)
        span.t1 = time.perf_counter() - self.epoch
        return span

    @contextmanager
    def span(self, name: str, kind: str = "step", *,
             parent: Optional[Span] = None, **attrs: Any):
        """Open + activate a span for the dynamic extent of the block: any
        span started inside (same thread) parents to it."""
        s = self.start_span(name, kind, parent=parent, **attrs)
        token = _CURRENT.set((self, s))
        try:
            yield s
        finally:
            _CURRENT.reset(token)
            if s.t1 is None:
                self.end(s)

    def clock_anchor(self) -> None:
        """Write this tracer's clock into the profiler's trace: a
        ``tracer.clock`` annotation whose ``tracer_ns`` stat is the
        tracer's reading (perf_counter ns since ``epoch``) at the
        annotation's entry, the midpoint of readings taken just before
        and just after it. The annotation's start in the trace is the same
        instant on the profiler's clock, so their difference maps every
        span onto the trace. Call it while the profiler records, once at
        each end of the traced window: two anchors show the drift. Spans
        themselves pay nothing for this."""
        import jax
        ann = jax.profiler.TraceAnnotation(CLOCK_ANCHOR)
        epoch_ns = int(self.epoch * 1e9)
        before = time.perf_counter_ns()
        ann.__enter__()
        after = time.perf_counter_ns()
        try:
            ann.set_metadata(tracer_ns=(before + after) // 2 - epoch_ns)
        finally:
            ann.__exit__(None, None, None)

    # -- reading / export --------------------------------------------------
    def spans(self) -> List[Span]:
        with self._lock:
            return list(self._spans)

    def by_id(self) -> Dict[int, Span]:
        return {s.span_id: s for s in self.spans()}

    def roots(self) -> List[Span]:
        return [s for s in self.spans() if s.parent_id is None]

    def children(self, span: Span) -> List[Span]:
        return [s for s in self.spans() if s.parent_id == span.span_id]

    def to_chrome(self) -> Dict[str, Any]:
        """Chrome trace event format (load in chrome://tracing/Perfetto).

        Complete ("X") events in microseconds; unfinished spans export with
        their open duration so a crashed run still renders."""
        now = time.perf_counter() - self.epoch
        events: List[Dict[str, Any]] = [{
            "ph": "M", "name": "process_name", "pid": 0, "tid": 0,
            "args": {"name": "repro-private-inference"}}]
        for s in self.spans():
            t1 = s.t1 if s.t1 is not None else now
            events.append({
                "name": s.name, "cat": s.kind, "ph": "X", "pid": 0,
                "tid": s.tid, "ts": round(s.t0 * 1e6, 3),
                "dur": round((t1 - s.t0) * 1e6, 3),
                "args": {**s.attrs, "trace_id": s.trace_id,
                         "span_id": s.span_id, "parent_id": s.parent_id}})
        # truncation marker: a bounded store drops the NEWEST spans once
        # full (children of stored parents may be missing) — consumers
        # must not read a truncated export as a connected tree
        return {"traceEvents": events, "displayTimeUnit": "ms",
                "otherData": {"epoch_unix": self.epoch_unix,
                              "dropped_spans": self.dropped,
                              "truncated": self.dropped > 0}}

    def dump_chrome(self, path) -> int:
        doc = self.to_chrome()
        with open(path, "w") as f:
            json.dump(doc, f, indent=1)
            f.write("\n")
        return len(doc["traceEvents"])

    def dump_jsonl(self, path) -> int:
        spans = self.spans()
        with open(path, "w") as f:
            for s in spans:
                f.write(json.dumps(s.as_dict()) + "\n")
            if self.dropped:
                # same truncation stamp the Chrome export carries — a
                # trailing marker line, so line-oriented consumers see it
                # without schema changes to the span records
                f.write(json.dumps({"truncated": True,
                                    "dropped_spans": self.dropped}) + "\n")
        return len(spans)


# -- ambient context -------------------------------------------------------
_CURRENT: ContextVar[Optional[Tuple[Tracer, Span]]] = ContextVar(
    "repro_trace_current", default=None)


def current_tracer() -> Optional[Tracer]:
    cur = _CURRENT.get()
    return cur[0] if cur is not None else None


def current_span() -> Optional[Span]:
    cur = _CURRENT.get()
    return cur[1] if cur is not None else None


@contextmanager
def activate(tracer: Optional[Tracer], span: Optional[Span] = None):
    """Install ``tracer`` (and optionally a current parent span) for the
    dynamic extent — the engine wraps each batch dispatch with this so the
    serving/executor/plane stages pick the tracer up ambiently. No-op when
    ``tracer`` is None."""
    if tracer is None or not tracer.enabled:
        yield None
        return
    token = _CURRENT.set((tracer, span))
    try:
        yield span
    finally:
        _CURRENT.reset(token)


@contextmanager
def maybe_span(name: str, kind: str = "step", **attrs: Any):
    """Ambient-span helper for instrumented call sites: records a child of
    the current span when a tracer is active, yields None (one contextvar
    read) otherwise."""
    cur = _CURRENT.get()
    if cur is None or not cur[0].enabled:
        yield None
        return
    with cur[0].span(name, kind, **attrs) as s:
        yield s


def annotate(span: Optional[Span], **attrs: Any) -> None:
    """Attach attributes to a ``maybe_span`` result (None-safe)."""
    if span is None:
        return
    tr = current_tracer()
    if tr is not None:
        tr.annotate(span, **attrs)
