"""Jitted public wrappers for the blinded modular matmul.

``field_matmul(x, w)`` takes field matrices in [0, p) (int32), handles limb
decomposition, padding to kernel block multiples, and backend selection:
Pallas-compiled on TPU, the pure-jnp reference elsewhere (bit-identical and
far faster than interpreted Pallas on CPU — the serving hot path), with
``impl="interpret"`` keeping the Pallas interpreter reachable for kernel
parity tests.

``fused_blinded_matmul`` is the single-chain fast path (DESIGN.md §6): one
Pallas pass that scales+quantizes+blinds+limb-encodes the activations, one
Pallas matmul whose epilogue unblinds and dequantizes in-register. With the
weight planes pre-encoded (``encode_weight_planes``, cached offline by
core/precompute.py) the blinded operand makes exactly one HBM round trip.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import tracing
from repro.kernels.blind.blind import blind_encode_pallas
from repro.kernels.limb_matmul import ref
from repro.kernels.limb_matmul.limb_matmul import (limb_matmul_planes,
                                                  limb_matmul_planes_fused)

_LANE = 128


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def _round_up(n: int, m: int) -> int:
    return ((n + m - 1) // m) * m


def _pad_to(x, m, axis):
    pad = (-x.shape[axis]) % m
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _fit_block(dim: int, target: int) -> int:
    """Largest block ≤ target that exactly tiles the lane-rounded dim.

    Pads only to the 128-lane multiple, never to a block multiple: a dim
    just over the default block (e.g. K=1152 with bk=1024) used to round up
    to 2·bk and nearly double the matmul work; instead shrink the block to
    an exact divisor (1152 -> 384×3)."""
    lanes = _round_up(dim, _LANE) // _LANE
    n = -(-lanes * _LANE // target)          # ceil-div to stay ≤ target
    while lanes % n:
        n += 1
    return lanes // n * _LANE


def block_plan(M: int, K: int, N: int, *, bm=256, bn=256, bk=1024):
    """Exact-fit blocks for the limb matmul grid.

    Returns (bm, bn, bk, Mp, Kp, Np) with each padded dim the 128-lane
    round-up of the operand dim and divisible by its block. The (K, N) half
    of the plan is independent of M, so weight planes encoded offline
    (core/precompute.py) line up with activations encoded per request.
    """
    bm = _fit_block(M, bm)
    bn = _fit_block(N, bn)
    bk = _fit_block(K, bk)
    return (bm, bn, bk,
            _round_up(M, _LANE), _round_up(K, _LANE), _round_up(N, _LANE))


def encode_weight_planes(w_field, *, bn=256, bk=1024):
    """(K, N) int32 field weights -> (3, Kp, Np) int8 limb planes, padded to
    the block plan. Done once per layer by the precompute cache."""
    K, N = w_field.shape
    _, bn_, bk_, _, _, _ = block_plan(1, K, N, bn=bn, bk=bk)
    wl = jnp.moveaxis(ref.to_limbs(ref.to_signed(w_field)), -1, 0)  # (3,K,N)
    return _pad_to(_pad_to(wl, bk_, 1), bn_, 2)


@functools.partial(jax.jit, static_argnames=("impl", "bm", "bn", "bk"))
def _field_matmul_jit(x_field, w_field, *, impl: str = "auto",
                      bm=256, bn=256, bk=1024):
    M, K = x_field.shape
    K2, N = w_field.shape
    assert K == K2
    # auto: the compiled kernel on TPU at every size; off-TPU the pure-jnp
    # reference (f32-exact limb GEMMs for K ≤ 2^10), which beats
    # interpreted Pallas by orders of magnitude and is bit-identical
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.field_matmul_ref(x_field, w_field)
    bm_, bn_, bk_, _, _, _ = block_plan(M, K, N, bm=bm, bn=bn, bk=bk)
    xl = jnp.moveaxis(ref.to_limbs(ref.to_signed(x_field)), -1, 0)  # (3,M,K)
    wl = jnp.moveaxis(ref.to_limbs(ref.to_signed(w_field)), -1, 0)  # (3,K,N)
    xl = _pad_to(_pad_to(xl, bm_, 1), bk_, 2)
    wl = _pad_to(_pad_to(wl, bk_, 1), bn_, 2)
    out = limb_matmul_planes(xl, wl, bm=bm_, bn=bn_, bk=bk_,
                             interpret=(impl == "interpret"))
    return out[:M, :N]


@functools.partial(jax.jit, static_argnames=("k_bits", "k_out_bits", "impl",
                                             "bm", "bn", "bk", "out_dtype"))
def _fused_blinded_matmul_jit(x, r, w_limbs, u, inv_scale, out_scale, *,
                              k_bits: int, k_out_bits: int,
                              impl: str = "auto", bm=256, bn=256, bk=1024,
                              out_dtype=jnp.float32):
    """Blind -> limb-encode -> field matmul -> unblind -> dequantize, fused.

    x: (M, K) float activations (unscaled); r: (M, K) int32 blinding stream;
    w_limbs: (3, Kp, Np) int8 pre-encoded weight planes
    (``encode_weight_planes``); u: (M, N) int32 precomputed unblinding
    factors (r @ W_q mod p over the *unpadded* dims); inv_scale: scalar f32
    reciprocal of the activation scale; out_scale: scalar f32 combined
    dequantization scale x_scale·w_scale·2^-k_out_bits.

    Returns (M, N) ``out_dtype``: dequant(unblind(blind(x/s) @ W)) · scale.
    Bit-identical across ref / interpret / compiled backends.
    """
    M, K = x.shape
    N = u.shape[1]
    bm_, bn_, bk_, Mp, Kp, Np = block_plan(M, K, N, bm=bm, bn=bn, bk=bk)
    assert w_limbs.shape == (3, Kp, Np), (w_limbs.shape, (3, Kp, Np))
    inv2 = jnp.asarray(inv_scale, jnp.float32).reshape(1, 1)
    sc2 = jnp.asarray(out_scale, jnp.float32).reshape(1, 1)
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        # pure-jnp path, same op order as the kernels (bit-exact);
        # selected off-TPU like _field_matmul_jit / _field_fold_jit
        # because interpreted Pallas pays per-element python dispatch
        from repro.kernels.blind.ref import blind_ref
        xs = x.astype(jnp.float32) * inv2[0, 0]
        w_f = ref.from_signed(
            ref.from_limbs(jnp.moveaxis(w_limbs[:, :K, :N], 0, -1)))
        y_b = ref.field_matmul_ref(blind_ref(xs, r, k_bits), w_f)
        s = ref.to_signed(ref.field_sub(y_b, u))
        return (s.astype(jnp.float32) * sc2[0, 0]).astype(out_dtype)
    interpret = impl == "interpret"
    if interpret and Kp > K:
        # interpret mode pays per-element python dispatch, so K-padding is
        # real work (compiled TPU lanes make it free): encode at natural K,
        # then pad the planes — bit-identical (zero x + zero r -> zero limbs)
        xl = blind_encode_pallas(_pad_to(x, bm_, 0), _pad_to(r, bm_, 0),
                                 inv2, k_bits, bm=bm_, bk=K, interpret=True)
        xl = _pad_to(xl, bk_, 2)
    else:
        xp = _pad_to(_pad_to(x, bm_, 0), bk_, 1)
        rp = _pad_to(_pad_to(r, bm_, 0), bk_, 1)
        xl = blind_encode_pallas(xp, rp, inv2, k_bits, bm=bm_, bk=bk_,
                                 interpret=interpret)
    up = _pad_to(_pad_to(u, bm_, 0), bn_, 1)
    y = limb_matmul_planes_fused(xl, w_limbs, up, sc2, bm=bm_, bn=bn_,
                                 bk=bk_, out_dtype=out_dtype,
                                 interpret=interpret)
    return y[:M, :N]


@functools.partial(jax.jit, static_argnames=("impl", "bm", "bk"))
def _field_fold_jit(x_field, s_field, *, impl: str = "auto", bm=256, bk=1024):
    """Freivalds fold ``(X @ S) mod p`` for a skinny fold matrix.

    x_field: (M, K) int32 in [0, p); s_field: (K, k) int32 in [0, p) with
    k ≤ 128 (the integrity layer uses k ∈ {1, 2}). Enclave-side cost of
    verifying a device matmul: one pass over X instead of a matmul grid
    (kernels/limb_matmul/fold.py); off-TPU the pure-jnp reference is both
    exact and faster than interpreted Pallas for these shapes.
    """
    from repro.kernels.limb_matmul.fold import FOLD_LANES, limb_fold_planes
    M, K = x_field.shape
    K2, kf = s_field.shape
    assert K == K2 and kf <= FOLD_LANES, (x_field.shape, s_field.shape)
    if impl == "ref" or (impl == "auto" and not _on_tpu()):
        return ref.field_matmul_ref(x_field, s_field)
    bm_, _, bk_, _, _, _ = block_plan(M, K, FOLD_LANES, bm=bm, bk=bk)
    xl = jnp.moveaxis(ref.to_limbs(ref.to_signed(x_field)), -1, 0)  # (3,M,K)
    sl = jnp.moveaxis(ref.to_limbs(ref.to_signed(s_field)), -1, 0)  # (3,K,kf)
    xl = _pad_to(_pad_to(xl, bm_, 1), bk_, 2)
    sl = _pad_to(_pad_to(sl, bk_, 1), FOLD_LANES, 2)
    out = limb_fold_planes(xl, sl, bm=bm_, bk=bk_,
                           interpret=(impl == "interpret"))
    return out[:M, :kf]


def field_matmul(x_field, w_field, **kw):
    """(X @ W) mod p. x: (M, K) int32 in [0, p); w: (K, N) int32 in [0, p).

    Thin profiling wrapper over the jitted kernel: when a tracer with
    kernel spans is ambient (core/tracing.py) and the operands are
    concrete, the call is fenced with ``block_until_ready`` on both sides
    and recorded as a ``kernel.limb_matmul`` span; otherwise it is the
    jitted call, untouched."""
    return tracing.profiled_kernel("kernel.limb_matmul", _field_matmul_jit,
                                   x_field, w_field, **kw)


def fused_blinded_matmul(x, r, w_limbs, u, inv_scale, out_scale, **kw):
    """Profiling wrapper over the fused chain (``kernel.fused_blind_matmul``
    spans cover blind_encode + limb matmul + in-register unblind)."""
    return tracing.profiled_kernel("kernel.fused_blind_matmul",
                                   _fused_blinded_matmul_jit, x, r, w_limbs,
                                   u, inv_scale, out_scale, **kw)


def field_fold(x_field, s_field, **kw):
    """Profiling wrapper over the jitted Freivalds fold (``kernel.fold``)."""
    return tracing.profiled_kernel("kernel.fold", _field_fold_jit,
                                   x_field, s_field, **kw)


def blinded_matmul(x_blinded, w_field, **kw):
    """Alias with protocol-level naming: the untrusted-device operation."""
    return field_matmul(x_blinded, w_field, **kw)
