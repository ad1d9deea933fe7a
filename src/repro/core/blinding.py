"""Cryptographic blinding: streams, quantized weights, unblinding factors.

The blinding stream ``r`` is a one-time pad over Z_p: uniform field elements
from a counter-based PRNG (threefry) keyed by (session_key, layer, step).
Because the stream is counter-derived, nothing has to be materialized ahead
of time or communicated between shards — each shard regenerates exactly its
slice (this is what makes blinding commute with pjit sharding, DESIGN.md §3).

Privacy argument (Slalom §4): for any x_q, (x_q + r) mod p with r ~ U(Z_p)
is itself uniform over Z_p, i.e. the untrusted device observes a perfect
one-time pad. Verified distributionally in tests/test_blinding.py.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro.kernels.blind.ops import blind, unblind
from repro.kernels.limb_matmul.ops import field_matmul
from repro.kernels.limb_matmul.ref import HALF, P, from_signed


@dataclass(frozen=True)
class BlindingSpec:
    """Quantization scales. Combined dot products must stay within ±HALF:
    K · 2^(k_act + k_w) · |x|·|w| < HALF — callers pick k for their fan-in."""
    k_act: int = 8
    k_w: int = 7


def stream_key(session_key: jax.Array, layer_id: int,
               step: int = 0) -> jax.Array:
    return jax.random.fold_in(jax.random.fold_in(session_key, layer_id), step)


def blinding_stream(key: jax.Array, shape: Tuple[int, ...]) -> jax.Array:
    """Uniform field elements in [0, p)."""
    return jax.random.randint(key, shape, 0, P, dtype=jnp.int32)


@functools.partial(jax.jit, static_argnames=("k_w",))
def _quantize_weight(w: jax.Array, k_w: int):
    wf = w.astype(jnp.float32)
    scale = jnp.maximum(jnp.max(jnp.abs(wf)), 1e-9)
    q = jnp.clip(jnp.round(wf / scale * (2.0 ** k_w)),
                 -HALF, HALF).astype(jnp.int32)
    return from_signed(q), scale


def quantize_weight(w: jax.Array, spec: BlindingSpec):
    """float weight -> (field representation, absmax scale).

    Per-tensor absmax scaling (enclave-side calibration, precomputed): the
    quantized integers use the full 2^k_w range regardless of weight
    magnitude. Returns (W_q in [0,p), scale) with
    W ≈ signed(W_q) · scale · 2^-k_w.

    A concrete weight is quantized by this one compiled program even while
    an enclosing trace is being built: the division is not correctly
    rounded on every backend (the TPU divides by reciprocal, XLA may fold
    constants on the host), so quantizing inside each caller's program
    gave the trusted recompute and the precompute cache different
    integers. Only traced weights (scanned blocks) quantize in-program.
    """
    with jax.ensure_compile_time_eval():
        return _quantize_weight(w, spec.k_w)


def unblinding_factor(r: jax.Array, w_q: jax.Array) -> jax.Array:
    """u = (r @ W_q) mod p — precomputed inside the enclave per Slalom.

    (Slalom stores these encrypted outside the enclave and pages slices in;
    our cost model accounts for that in core/trust.py.)
    """
    return field_matmul(r, w_q)


def blind_activations(x: jax.Array, r: jax.Array,
                      spec: BlindingSpec) -> jax.Array:
    return blind(x, r, spec.k_act)


def unblind_result(y_b: jax.Array, u: jax.Array, spec: BlindingSpec,
                   out_dtype=jnp.float32) -> jax.Array:
    return unblind(y_b, u, spec.k_act + spec.k_w, out_dtype)
