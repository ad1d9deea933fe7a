"""Slalom protocol: per-linear-op blinded offload (the tier-1 inner loop).

``blinded_dense(p, x, ...)`` is a drop-in for models.layers.dense:

    enclave:   x_q = Quant(x);  x_b = (x_q + r) mod p
    device:    y_b = (x_b @ W_q) mod p            <- limb_matmul kernel
    enclave:   y   = Dequant((y_b - r@W_q) mod p) (+ bias, fp)

The protocol only applies to *static-weight* linear maps (unblinding needs
the precomputable r·W) — exactly Slalom's constraint; attention cores,
recurrences and non-linearities stay in the enclave during tier-1
(DESIGN.md §3, §5).

Two data-path implementations (``SlalomContext.impl``):

- ``"fused"`` (default): one Pallas pass blinds + limb-encodes the
  activations, the limb matmul's epilogue unblinds + dequantizes
  in-register — the blinded operand makes exactly one HBM round trip
  (DESIGN.md §6).
- ``"unfused"``: the seed path (separate blind, limb-decompose, matmul,
  unblind passes), kept selectable for benchmarks/blinding_micro.py.

When ``SlalomContext.factors`` is set (core/precompute.py), the weight
quantization/limb encoding and the unblinding-factor matmul ``u = r @ W_q``
are *precomputed off the request path* — the traced request performs exactly
one device field-matmul per blinded op, mirroring the paper's offline
enclave precomputation. ``Telemetry.device_matmuls``/``enclave_matmuls``
count both kinds so tests can verify the claim.

Integrity (PR 3, DESIGN.md §9): the device result is *verified*, not just
trusted — ``ctx.integrity`` threads a Freivalds policy (core/integrity.py)
through every blinded op, ``ctx.fault`` injects a dishonest device
(runtime/faults.py) underneath it, and ``ctx.trusted`` switches the op to
an enclave-resident field matmul (the recovery path: bit-identical output,
no device, no blinding needed).

A trace-time ``Telemetry`` recorder accumulates blinded bytes / offloaded
FLOPs / enclave FLOPs per protocol call — shapes are static under jit, so
this is exact and free; core/trust.py turns it into the paper's cost model.
"""
from __future__ import annotations

from contextlib import contextmanager
from dataclasses import dataclass, field as dfield
from typing import Any, List, Optional

import jax
import jax.numpy as jnp

from repro.core import blinding as B
from repro.core import integrity as IG
from repro.core import tracing
from repro.kernels.blind.ref import quantize as quantize_act
from repro.kernels.limb_matmul.ops import (encode_weight_planes, field_matmul,
                                           fused_blinded_matmul)
from repro.kernels.limb_matmul.ref import P, from_signed, to_signed

# fault keys live in their own fold_in domain, disjoint from both the
# blinding streams and the verify keys (core/integrity.py)
FAULT_DOMAIN = 0xFA17


@dataclass
class Telemetry:
    """Static-shape accounting gathered while tracing (bytes, FLOPs)."""
    blinded_bytes: int = 0          # enclave->device blinded traffic
    returned_bytes: int = 0         # device->enclave results
    offloaded_flops: int = 0        # linear-op FLOPs run untrusted
    enclave_flops: int = 0          # blinding/unblinding elementwise work
    enclave_peak_feature_bytes: int = 0
    calls: int = 0
    device_matmuls: int = 0         # field matmuls in the request trace
    enclave_matmuls: int = 0        # r@W_q factor matmuls in the trace
                                    # (0 when the precompute cache is active)
    verify_ops: int = 0             # blinded ops with verification in-trace
    verify_flops: int = 0           # fold-check work (enclave-side)
    fold_matmuls: int = 0           # on-request W_q@s folds (0 when the
                                    # precompute cache carries the vectors)
    trusted_matmuls: int = 0        # enclave-recompute field matmuls

    def record_verify(self, t: int, d_in: int, d_out: int, k: int):
        self.verify_ops += 1
        self.verify_flops += 2 * k * t * (d_in + d_out)

    def record_trusted(self, t: int, d_in: int, d_out: int):
        self.trusted_matmuls += 1
        self.enclave_flops += 2 * t * d_in * d_out

    def record_offload(self, t: int, d_in: int, d_out: int):
        self.blinded_bytes += t * d_in * 4
        self.returned_bytes += t * d_out * 4
        self.offloaded_flops += 2 * t * d_in * d_out
        # blind + unblind touch every element once each
        self.enclave_flops += 2 * t * (d_in + d_out)
        self.enclave_peak_feature_bytes = max(
            self.enclave_peak_feature_bytes, t * max(d_in, d_out) * 4)
        self.calls += 1


@dataclass
class SlalomContext:
    """Session state for one private-inference request.

    ``factors``: per-layer precomputed blinding material from
    ``BlindedLayerCache.session_factors`` (consumed positionally, in call
    order). ``integrity``/``fault``: Freivalds policy and dishonest-device
    injector (core/integrity.py, runtime/faults.py); ``integrity_log``
    collects one (checked, failed, corrupted) bool triple per blinded op.
    ``trusted``: enclave-recompute mode — no device, no blinding, no
    verification. ``unblinded``: verified-open offload (core/plan.py) —
    the device gets the quantized operand with a ZERO pad (no privacy) and
    the factor matmul vanishes (u = 0·W); verification still applies.
    ``plane``: a parallel/offload_sharding.OffloadPlane — when set, the
    device field matmul of every per-op-addressable blinded op shards
    across the plane's DevicePool (shard-local Freivalds, per-device
    health); ``shard`` is the per-segment ShardPolicy override.
    ``integrity``/``unblinded``/``shard`` are per-plan-segment state: the
    plan interpreter scopes them with ``segment_overrides`` while tracing.
    """
    session_key: jax.Array
    spec: B.BlindingSpec = dfield(default_factory=B.BlindingSpec)
    telemetry: Telemetry = dfield(default_factory=Telemetry)
    # stream-key step component. An int for single-shot traces; the decode
    # interpreter (core/origami.py) sets it to the TRACED token position so
    # one compiled token-step executable draws fresh per-token pads, fold
    # vectors and sampling decisions (fold_in accepts traced ints).
    step: Any = 0
    impl: str = "fused"                       # "fused" | "unfused"
    factors: Optional[List[Any]] = None
    integrity: IG.IntegrityPolicy = dfield(
        default_factory=IG.IntegrityPolicy.off)
    fault: Optional[Any] = None               # runtime/faults.DishonestDevice
    trusted: bool = False
    unblinded: bool = False
    plane: Optional[Any] = None               # offload_sharding.OffloadPlane
    shard: Optional[Any] = None               # plan.ShardPolicy override
    # per-op addressability verdict override. The default (None) infers
    # "scanned" from the weight leaf being a tracer — right for forward
    # traces, where a tracer weight means lax.scan over stacked blocks.
    # The decode interpreter unrolls the block walk at trace time, so its
    # weights are tracers (jit args) yet every op IS individually
    # addressable: it sets per_op=True and verification/injection bind.
    per_op: Optional[bool] = None
    integrity_log: List[Any] = dfield(default_factory=list)
    _layer_counter: int = 0

    @contextmanager
    def segment_overrides(self, integrity: Optional[IG.IntegrityPolicy],
                          unblinded: bool = False, shard: Optional[Any] = None):
        """Scope the effective verification policy / unblinded flag /
        shard policy to one plan segment (trace-time Python state, static
        under jit)."""
        prev = self.integrity, self.unblinded, self.shard
        if integrity is not None:
            self.integrity = integrity
        self.unblinded = unblinded
        if shard is not None:
            self.shard = shard
        try:
            yield self
        finally:
            self.integrity, self.unblinded, self.shard = prev

    def next_layer_key(self) -> jax.Array:
        k = B.stream_key(self.session_key, self._layer_counter, self.step)
        self._layer_counter += 1
        return k

    def fault_key(self, op_index: int) -> jax.Array:
        return B.stream_key(
            jax.random.fold_in(self.session_key, FAULT_DOMAIN),
            op_index, self.step)

    def next_layer_factors(self, t: int, d_in: int, d_out: int, w):
        """Blinding + verification material for the next blinded op.

        Returns (w_q, w_scale, w_limbs_or_None, r, u, s, ws, shard_folds).
        The cached branch issues no field matmul; the on-the-fly branch
        issues one for ``u`` (telemetry.enclave_matmuls) and, when
        verification is on and the cache carries no fold vectors, one
        skinny ``W_q @ s`` fold. ``shard_folds`` is the per-shard
        (s_j, ws_j) list the offload plane consumes (prefetched by the
        cache when its ``shards`` > 1; the plane derives it live otherwise).
        """
        op = self._layer_counter
        sf = None
        if self.factors is not None:
            assert op < len(self.factors), (
                f"precompute cache has {len(self.factors)} layers but the "
                f"trace reached blinded op #{op} — rebuild the cache for "
                f"this batch shape/partition")
            self._layer_counter += 1
            e = self.factors[op]
            w_q, w_scale = e["w_q"], e["w_scale"]
            w_limbs, r, u = e.get("w_limbs"), e["r"], e["u"]
            if r is None:
                # verified-open slot (precompute.py stores no arrays for
                # the zero pad): synthesize it in-trace — a jit constant,
                # not per-session device memory
                r = jnp.zeros((t, d_in), jnp.int32)
                u = jnp.zeros((t, d_out), jnp.int32)
            else:
                assert e["r"].shape == (t, d_in), (
                    f"cached stream shape {e['r'].shape} != ({t}, {d_in}) — "
                    f"cache was built for a different batch shape")
            s, ws = e.get("s"), e.get("ws")
            sf = e.get("shard_folds")
        elif self.unblinded:
            # verified-open offload: zero pad, so u = (0 @ W) = 0 — no
            # factor matmul exists to pay for (or precompute)
            self._layer_counter += 1
            w_q, w_scale = B.quantize_weight(w, self.spec)
            r = jnp.zeros((t, d_in), jnp.int32)
            u = jnp.zeros((t, d_out), jnp.int32)
            w_limbs = s = ws = None
        else:
            key = self.next_layer_key()
            w_q, w_scale = B.quantize_weight(w, self.spec)
            with jax.named_scope(tracing.FACTORS_PAD):
                r = B.blinding_stream(key, (t, d_in))
            with jax.named_scope(tracing.FACTORS_UNBLIND):
                u = B.unblinding_factor(r, w_q)  # on-request (Slalom does
            self.telemetry.enclave_matmuls += 1  # this offline; see
            w_limbs = s = ws = None              # precompute.py)
        if self.integrity.enabled and s is None:
            # same derivation as BlindedLayerCache.session_factors, so the
            # cached and live verification traces are bit-identical
            with jax.named_scope(tracing.FACTORS_FOLD):
                s = IG.fold_stream(self.session_key, op, self.step,
                                   d_out, self.integrity.k)
                ws = field_matmul(w_q, s)
            self.telemetry.fold_matmuls += 1    # on the request path — the
            self.telemetry.verify_flops += (    # cache moves these offline
                2 * d_in * d_out * self.integrity.k)
        return w_q, w_scale, w_limbs, r, u, s, ws, sf


def blinded_dense(ctx: SlalomContext, p, x, scanned: Optional[bool] = None):
    """Drop-in for layers.dense running the Slalom protocol.

    p: {"w": (d_in, d_out) float [, "b": (d_out,)]}; x: (..., d_in).
    ``scanned``: whether this op's weight leaf is a lax.scan tracer (one
    traced call standing for many runtime layers); None = infer from ``w``
    itself — callers that transform the weight first (blinded_conv2d's
    im2col reorder turns a concrete leaf into a tracer) must pass the
    verdict on the RAW leaf.
    """
    # per-op trace span — eager traces only (plane path / recoveries);
    # attributes are shapes and placement flags, never operands
    if not isinstance(x, jax.core.Tracer):
        with tracing.maybe_span(
                "op.trusted" if ctx.trusted else "op.blinded", "step",
                layer=ctx._layer_counter, d_in=int(p["w"].shape[0]),
                d_out=int(p["w"].shape[1]),
                verified_open=bool(ctx.unblinded)):
            return _blinded_dense(ctx, p, x, scanned)
    return _blinded_dense(ctx, p, x, scanned)


def _blinded_dense(ctx: SlalomContext, p, x,
                   scanned: Optional[bool] = None):
    w = p["w"]
    d_in, d_out = w.shape
    lead = x.shape[:-1]
    t = 1
    for s in lead:
        t *= s
    xt = x.reshape(t, d_in)

    spec = ctx.spec
    k_out = spec.k_act + spec.k_w
    op_index = ctx._layer_counter

    if ctx.trusted:
        # --- enclave recompute (integrity recovery / quarantined backend):
        # the enclave performs the field matmul itself. Blinding would
        # cancel exactly ((x_b@W − r@W) mod p == (x_q@W) mod p), so it is
        # skipped; the quantized math and float op order match the blinded
        # data path bit-for-bit, which is what makes a recovered response
        # indistinguishable from an honest device's (tests/test_integrity).
        ctx._layer_counter += 1
        w_q, w_scale = B.quantize_weight(w, spec)
        with jax.named_scope(tracing.TIER1_BLIND):
            x_scale = jnp.maximum(jnp.max(jnp.abs(xt.astype(jnp.float32))),
                                  1e-9)
            # fused blinds with multiply-by-reciprocal, unfused with
            # division — replicate the active impl so the recompute stays
            # bit-identical
            xs = (xt.astype(jnp.float32) * (1.0 / x_scale)
                  if ctx.impl == "fused" else xt.astype(jnp.float32) / x_scale)
            x_field = from_signed(quantize_act(xs, spec.k_act))
        with jax.named_scope(tracing.TIER1_MATMUL):
            y_field = field_matmul(x_field, w_q)
        with jax.named_scope(tracing.TIER1_UNBLIND):
            y = (to_signed(y_field).astype(jnp.float32)
                 * (x_scale * w_scale)) * (2.0 ** -k_out)
            if "b" in p:
                y = y + p["b"].astype(jnp.float32)
        ctx.telemetry.record_trusted(t, d_in, d_out)
        return y.reshape(lead + (d_out,)).astype(x.dtype)

    # --- enclave: weight quantization + blinding material (precomputed when
    # the cache is active, otherwise derived on the request path) ---
    w_q, w_scale, w_limbs, r, u, s, ws, sf = ctx.next_layer_factors(
        t, d_in, d_out, w)
    # verification/injection cannot bind per-op state for ops traced inside
    # lax.scan (one traced call stands for many runtime layers, and traced
    # values appended to integrity_log would leak out of the scan) — same
    # restriction as the precompute cache; such ops stay unverified. The
    # decode interpreter's unrolled walk overrides the verdict via
    # ctx.per_op: its weights are jit-arg tracers but each traced call
    # stands for exactly one runtime op (DESIGN.md §16).
    if scanned is None:
        if ctx.per_op is not None:
            scanned = not ctx.per_op
        else:
            scanned = isinstance(w, jax.core.Tracer)
    # --- enclave: per-request absmax activation scale ---
    with jax.named_scope(tracing.TIER1_BLIND):
        x_scale = jnp.maximum(jnp.max(jnp.abs(xt.astype(jnp.float32))), 1e-9)
    if ctx.plane is not None and not scanned:
        # --- multi-device plane: the device matmul shards across the pool
        # (parallel/offload_sharding.py) with shard-local Freivalds checks,
        # single-shard retry and straggler hedging — host-side control
        # flow, so the executor runs this trace eagerly (core/origami.py).
        # Faults are per-device (pool slots), not executor-wide, and every
        # shard is checked, so the op-level log records a verified op with
        # no *unrecovered* failure (the plane's ShardReport carries
        # detection/retry counts and the pool the per-device health).
        k_out = spec.k_act + spec.k_w
        with jax.named_scope(tracing.TIER1_BLIND):
            if ctx.impl == "fused":
                # replicate the fused kernel's quantization exactly
                # (multiply by reciprocal; kernels/blind/ref.py is the
                # kernel oracle) so the sharded result is bit-identical to
                # fused_blinded_matmul
                xs = xt.astype(jnp.float32) * (1.0 / x_scale)
            else:
                xs = xt.astype(jnp.float32) / x_scale
            x_b = jnp.mod(from_signed(quantize_act(xs, spec.k_act)) + r, P)
        with jax.named_scope(tracing.TIER1_MATMUL):
            y_b = ctx.plane.matmul(
                x_b, w_q, session_key=ctx.session_key, op_index=op_index,
                step=ctx.step,
                k=ctx.integrity.k if ctx.integrity.enabled else 1,
                folds=sf,
                mode=ctx.shard.mode if ctx.shard is not None else None,
                group=ctx.shard.devices if ctx.shard is not None else None)
        with jax.named_scope(tracing.TIER1_UNBLIND):
            if ctx.impl == "fused":
                out_scale = x_scale * w_scale * (2.0 ** -k_out)
                y = (to_signed(jnp.mod(y_b - u + P, P)).astype(jnp.float32)
                     * out_scale)
            else:
                y = B.unblind_result(y_b, u, spec, out_dtype=jnp.float32)
                y = y * (x_scale * w_scale)
            if "b" in p:
                y = y + p["b"].astype(jnp.float32)
        ctx.integrity_log.append((jnp.bool_(True), jnp.bool_(False),
                                  jnp.bool_(False)))
        ctx.telemetry.record_verify(t, d_in, d_out,
                                    ctx.integrity.k
                                    if ctx.integrity.enabled else 1)
        ctx.telemetry.device_matmuls += 1
        ctx.telemetry.record_offload(t, d_in, d_out)
        return y.reshape(lead + (d_out,)).astype(x.dtype)

    verify = ctx.integrity.enabled and not scanned
    inject = ctx.fault is not None and not scanned
    will_check = (IG.decide(ctx.integrity, ctx.session_key, op_index,
                            ctx.step) if verify or inject else None)
    checked = failed = corrupted = None
    if ctx.impl == "fused":
        if w_limbs is None:
            w_limbs = encode_weight_planes(w_q)
        out_scale = x_scale * w_scale * (2.0 ** -k_out)
        # one kernel chain (blind-encode pass, matmul, in-register unblind
        # epilogue), one device phase; tier1.blind here is the absmax scale
        with jax.named_scope(tracing.TIER1_MATMUL):
            y = fused_blinded_matmul(
                xt.astype(jnp.float32), r, w_limbs, u, 1.0 / x_scale,
                out_scale, k_bits=spec.k_act, k_out_bits=k_out)
        if verify or inject:
            with jax.named_scope(tracing.TIER1_VERIFY):
                # the fused kernel unblinds+dequantizes in-register; recover
                # the signed field result exactly (|y_q| ≤ HALF < 2^22 and
                # the only inexact step is one f32 multiply, so round()
                # inverts it)
                y_q = jnp.round(y / out_scale).astype(jnp.int32)
                y_field = from_signed(y_q)
                if inject:
                    y_field, corrupted = ctx.fault.corrupt(
                        y_field, op_index=op_index,
                        key=ctx.fault_key(op_index), will_verify=will_check)
                if verify:
                    # post-unblind identity: y_q @ s ≡ x_q @ ws (mod p); x_q
                    # is the enclave's own quantization of its own
                    # activations (bit-identical to the kernel's: same
                    # reciprocal, same round/clip — kernels/blind/ref.py is
                    # the kernel oracle)
                    x_field = from_signed(quantize_act(
                        xt.astype(jnp.float32) * (1.0 / x_scale), spec.k_act))
                    checked, failed = IG.checked_pair(
                        y_field, x_field, s, ws, will_check,
                        always=ctx.integrity.mode == "full")
            with jax.named_scope(tracing.TIER1_UNBLIND):
                y = to_signed(y_field).astype(jnp.float32) * out_scale
    else:
        # --- seed path: blind, device field-matmul, unblind (3 HBM trips) ---
        with jax.named_scope(tracing.TIER1_BLIND):
            x_b = B.blind_activations(xt.astype(jnp.float32) / x_scale, r,
                                      spec)
        with jax.named_scope(tracing.TIER1_MATMUL):
            y_b = field_matmul(x_b, w_q)
            if inject:
                y_b, corrupted = ctx.fault.corrupt(
                    y_b, op_index=op_index, key=ctx.fault_key(op_index),
                    will_verify=will_check)
        if verify:
            # blinded-domain identity: y_b @ s ≡ x_b @ ws (mod p)
            checked, failed = IG.checked_pair(
                y_b, x_b, s, ws, will_check,
                always=ctx.integrity.mode == "full")
        with jax.named_scope(tracing.TIER1_UNBLIND):
            y = B.unblind_result(y_b, u, spec, out_dtype=jnp.float32)
            y = y * (x_scale * w_scale)
    if verify or inject:
        false = jnp.bool_(False)
        ctx.integrity_log.append((
            checked if checked is not None else false,
            failed if failed is not None else false,
            corrupted if corrupted is not None else false))
        if verify:
            ctx.telemetry.record_verify(t, d_in, d_out, ctx.integrity.k)
    ctx.telemetry.device_matmuls += 1
    if "b" in p:
        with jax.named_scope(tracing.TIER1_UNBLIND):
            y = y + p["b"].astype(jnp.float32)
    ctx.telemetry.record_offload(t, d_in, d_out)
    return y.reshape(lead + (d_out,)).astype(x.dtype)


def extract_patches(x, kh: int, kw: int, stride: int = 1):
    """NHWC SAME patch extraction as one strided-slice XLA op.

    Returns (B·Ho·Wo, cin·kh·kw) with channel-major ordering (c, i, j) —
    pair with ``conv_weight_cols``. Replaces the kh·kw-times-materialized
    Python-loop im2col (which built kh·kw full-size slices and concatenated
    them in HBM before blinding). The patch conv runs at HIGHEST precision:
    at the TPU's default it rounds every activation to bfloat16 before
    quantization.
    """
    with jax.named_scope(tracing.TIER1_PATCHES):
        patches = jax.lax.conv_general_dilated_patches(
            x, (kh, kw), (stride, stride), "SAME",
            dimension_numbers=("NHWC", "HWIO", "NHWC"),
            precision=jax.lax.Precision.HIGHEST)
        return patches.reshape(-1, patches.shape[-1]), patches.shape[:3]


def conv_weight_cols(w):
    """(kh, kw, cin, cout) -> (cin·kh·kw, cout), matching extract_patches."""
    kh, kw, cin, cout = w.shape
    return jnp.transpose(w, (2, 0, 1, 3)).reshape(cin * kh * kw, cout)


def blinded_conv2d(ctx: SlalomContext, p, x, stride: int = 1):
    """Blinded 3x3 SAME conv via patch extraction -> blinded matmul.

    On TPU convolutions lower to MXU matmuls anyway; im2col + limb matmul is
    the faithful field-arithmetic equivalent. The patch tensor feeds the
    fused blind->limb-encode kernel directly.
    """
    w = p["w"]                                # (kh, kw, cin, cout)
    kh, kw, cin, cout = w.shape
    xcol, out_hw = extract_patches(x, kh, kw, stride)
    # a concrete weight stays concrete, so it quantizes exactly as the
    # precompute cache's copy does (blinding.quantize_weight)
    with jax.ensure_compile_time_eval():
        w_cols = conv_weight_cols(w)
    y = blinded_dense(ctx, {"w": w_cols, "b": p["b"]}, xcol,
                      scanned=isinstance(w, jax.core.Tracer))
    return y.reshape(out_hw + (cout,))
