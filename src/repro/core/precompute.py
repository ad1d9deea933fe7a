"""Precomputed blinding pipeline: weight quantization + unblinding factors
off the request path (DESIGN.md §4).

The paper's enclave precomputes the unblinding factors ``u = (r @ W_q) mod
p`` offline and pages them in during inference — that precomputation is what
makes blinded offload cheaper than enclave-resident compute. The seed
implementation instead re-derived both the quantized weights *and* ``u``
inside every traced request, so each "offloaded" matmul was paid twice
(once blinded on the device, once in the enclave).

``BlindedLayerCache`` fixes both halves:

- **Weights, once per model** (``from_records``): per blinded op, the field
  weights ``w_q``, the absmax scale, and the pre-encoded int8 limb planes
  (padded to the matmul block plan) are computed at executor construction
  and reused by every request.
- **Streams/factors, once per (session, layer, step)**
  (``session_factors``): the blinding stream ``r`` and factor ``u`` are
  generated off the request path. ``prefetch`` enqueues the next session's
  factors while the current batch runs on device (JAX async dispatch
  overlaps them — the double-buffering runtime/serving.py drives); ``take``
  pops a prefetched set or falls back to computing synchronously, and
  counts both (``factors_taken``, ``factors_sync``; the fallback records a
  ``factors.sync`` span). Each layer's set is one compiled program whose
  ops run under the device phases ``factors.pad`` (r), ``factors.unblind``
  (u = r·W_q) and ``factors.fold`` (s, W_q·s) of core/tracing.py.

Factor keying is ``stream_key(session_key, layer_index, step)`` — exactly
the stream the on-the-fly path draws, so cached and uncached traces are
bit-identical (tests/test_precompute.py), and distinct (session, layer,
step) triples never reuse a pad.

Integrity (PR 3): when the owning executor runs a Freivalds policy
(``integrity.enabled``), each factor set also carries the fold vectors
``s`` (uniform over Z_p^(d_out × k)) and ``ws = (W_q @ s) mod p`` — the
per-(session, layer) material of the verification layer (core/integrity.py,
DESIGN.md §9). They ride the same prefetch ring, so with the SessionPool
active the skinny fold matmuls are off the request path too.
"""
from __future__ import annotations

import functools
import threading
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np

from repro.core import blinding as B
from repro.core import integrity as IG
from repro.core import tracing
from repro.kernels.limb_matmul.ops import encode_weight_planes, field_matmul


@dataclass(frozen=True)
class CachedLayer:
    """Per-blinded-op static material (weights are static across requests).

    ``unblinded``: verified-open offload slot (core/plan.py) — the pad is
    identically zero (no privacy, no factor matmul), fold vectors still
    apply. ``policy``: this op's Freivalds policy (``None`` inherits the
    cache-wide one), the plan's per-step integrity override."""
    t: int                      # activation rows (batch-shape dependent)
    d_in: int
    d_out: int
    w_q: jax.Array              # (d_in, d_out) int32 field
    w_limbs: jax.Array          # (3, Kp, Np) int8, padded to the block plan
    w_scale: jax.Array          # () float32 absmax scale
    unblinded: bool = False
    policy: Optional[IG.IntegrityPolicy] = None


@functools.partial(jax.jit, static_argnames=("layer", "t", "d_in", "d_out",
                                             "pad", "k", "shards"))
def _layer_factors(session_key, step, w_q, *, layer: int, t: int, d_in: int,
                   d_out: int, pad: bool, k: int, shards: int
                   ) -> Dict[str, Any]:
    """One layer's session factors as one program: the pad r and u = r·W_q
    (``pad``), the fold vectors s and W_q·s (``k`` > 0), and ``shards``
    per-shard fold pairs (``shards`` > 1, ``k`` columns, at least one).
    The same stream keys and the same ops as the live derivation in
    core/slalom.py, so the bits are the same."""
    out: Dict[str, Any] = {}
    if pad:
        with jax.named_scope(tracing.FACTORS_PAD):
            out["r"] = B.blinding_stream(B.stream_key(session_key, layer,
                                                      step), (t, d_in))
        with jax.named_scope(tracing.FACTORS_UNBLIND):
            out["u"] = field_matmul(out["r"], w_q)
    with jax.named_scope(tracing.FACTORS_FOLD):
        if k:
            out["s"] = IG.fold_stream(session_key, layer, step, d_out, k)
            out["ws"] = field_matmul(w_q, out["s"])
        if shards > 1:
            folds = []
            for j in range(shards):
                s_j = IG.shard_fold_stream(session_key, layer, step, j,
                                           d_out, max(k, 1))
                folds.append((s_j, field_matmul(w_q, s_j)))
            out["shard_folds"] = folds
    return out


class BlindedLayerCache:
    """Quantize-once weight cache + per-session blinding-factor store."""

    def __init__(self, layers: List[CachedLayer], spec: B.BlindingSpec,
                 integrity: Optional[IG.IntegrityPolicy] = None):
        self.layers = layers
        self.spec = spec
        self.integrity = integrity or IG.IntegrityPolicy.off()
        # > 1 when the owning executor runs a multi-device offload plane
        # (core/origami.py sets it to the plane's shard count): each factor
        # set then also carries per-shard Freivalds fold vectors, so the
        # SessionPool ring keeps shard-local verification material off the
        # request path alongside (r, u)
        self.shards = 1
        self.factor_matmuls = 0          # r@W_q matmuls issued off-path
        self.fold_matmuls = 0            # W_q@s fold matmuls issued off-path
        self.factors_taken = 0           # sets handed to a request (take)
        self.factors_sync = 0            # ... of them generated by take itself
        self.bucket: Optional[int] = None  # batch rows (set by the executor)
        self._ready: Dict[Tuple[bytes, int], List[Dict[str, Any]]] = {}
        # prefetch/take race under the serving engine: the SessionPool's
        # refill thread inserts while the batcher thread pops
        self._lock = threading.Lock()

    @classmethod
    def from_records(cls, records: List[Dict[str, Any]],
                     spec: B.BlindingSpec,
                     integrity: Optional[IG.IntegrityPolicy] = None
                     ) -> "BlindedLayerCache":
        """records: static per-op descriptors in trace order — one
        {"kind", "w", "t", "d_in", "d_out"} per offloaded op (derived from
        the PlacementPlan's cache slots by models/vgg.py:
        blinded_op_records; the eval_shape recorder re-trace is gone).
        Optional keys: "unblinded" (verified-open slot) and "policy"
        (per-step Freivalds override). Conv records carry the raw
        (kh, kw, cin, cout) weight; the im2col column reorder happens
        here, outside any trace."""
        from repro.core.slalom import conv_weight_cols
        layers = []
        for rec in records:
            w = (conv_weight_cols(rec["w"]) if rec["kind"] == "conv"
                 else rec["w"])
            w_q, w_scale = B.quantize_weight(w, spec)
            layers.append(CachedLayer(
                t=rec["t"], d_in=rec["d_in"], d_out=rec["d_out"],
                w_q=w_q, w_limbs=encode_weight_planes(w_q),
                w_scale=w_scale,
                unblinded=bool(rec.get("unblinded", False)),
                policy=rec.get("policy")))
        return cls(layers, spec, integrity=integrity)

    # -- per-session factors -----------------------------------------------
    @staticmethod
    def _skey(session_key, step: int) -> Tuple[bytes, int]:
        return np.asarray(session_key).tobytes(), step

    def session_factors(self, session_key, step: int = 0) -> List[Dict]:
        """Generate (r, u) — and, under an integrity policy, the Freivalds
        fold vectors (s, ws) — for every cached layer: the enclave's offline
        work. Returned as a jit-passable pytree (list of dicts of arrays)
        consumed positionally by SlalomContext."""
        factors = []
        for i, lyr in enumerate(self.layers):
            # verified-open slot (``unblinded``): zero pad, u = (0 @ W) = 0
            # — nothing to matmul or store. The entry keeps its positional
            # slot with r/u = None; the consumer synthesizes the zeros
            # inside the trace (core/slalom.py), so a prefetch ring never
            # pins full-size constant-zero arrays per session.
            pol = lyr.policy if lyr.policy is not None else self.integrity
            k = pol.k if pol.enabled else 0
            # ``shards`` > 1: per-shard fold vectors for the offload plane
            # — shards are ALWAYS checked (k falls back to 1 with the
            # policy off); derivation matches integrity.shard_fold_stream
            # so cached and live shard verification are bit-identical
            entry = {"r": None, "u": None, "w_q": lyr.w_q,
                     "w_limbs": lyr.w_limbs, "w_scale": lyr.w_scale}
            entry.update(_layer_factors(
                session_key, step, lyr.w_q, layer=i, t=lyr.t, d_in=lyr.d_in,
                d_out=lyr.d_out, pad=not lyr.unblinded, k=k,
                shards=self.shards))
            self.factor_matmuls += not lyr.unblinded
            self.fold_matmuls += bool(k) + (self.shards
                                            if self.shards > 1 else 0)
            factors.append(entry)
        return factors

    # prefetched sets a session's r tensors can pin ~100s of MB for large
    # models; double-buffering needs exactly one set in flight — keep 2 for
    # slack and evict FIFO so an abandoned session can't pin factors
    # forever. The serving engine's SessionPool raises this to its pool
    # depth via ``max_prefetched`` (runtime/sessions.py).
    MAX_PREFETCHED = 2

    @property
    def max_prefetched(self) -> int:
        return getattr(self, "_max_prefetched", self.MAX_PREFETCHED)

    @max_prefetched.setter
    def max_prefetched(self, n: int) -> None:
        self._max_prefetched = max(1, int(n))

    def prefetch(self, session_key, step: int = 0) -> None:
        """Enqueue factor generation for a future session (async dispatch:
        returns immediately, compute overlaps whatever runs on device)."""
        k = self._skey(session_key, step)
        with self._lock:
            if k in self._ready:
                return
        factors = self.session_factors(session_key, step)
        with self._lock:
            while len(self._ready) >= self.max_prefetched:
                self._ready.pop(next(iter(self._ready)))
            self._ready.setdefault(k, factors)

    def prefetched(self, session_key, step: int = 0) -> bool:
        with self._lock:
            return self._skey(session_key, step) in self._ready

    def clear_prefetch(self) -> None:
        """Drop all buffered factor sets (e.g. when a server goes idle)."""
        with self._lock:
            self._ready.clear()

    def take(self, session_key, step: int = 0) -> List[Dict]:
        """Pop prefetched factors for this session, or compute them now
        (counted, and recorded as a ``factors.sync`` span: the request
        waits for them)."""
        with self._lock:
            hit = self._ready.pop(self._skey(session_key, step), None)
            self.factors_taken += 1
            self.factors_sync += hit is None
        if hit is not None:
            return hit
        with tracing.maybe_span("factors.sync", "session",
                                layers=len(self.layers), bucket=self.bucket):
            return self.session_factors(session_key, step)

    @property
    def num_layers(self) -> int:
        return len(self.layers)

    def weight_bytes(self) -> int:
        """Cache footprint of the static half (w_q + limb planes + scale)."""
        tot = 0
        for lyr in self.layers:
            tot += lyr.w_q.size * 4 + lyr.w_limbs.size + 4
        return tot
