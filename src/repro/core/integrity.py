"""Freivalds-verified offload: probabilistic integrity checks over the
untrusted field matmul (DESIGN.md §9).

Blinding (core/blinding.py) hides tier-1 activations from the device but
does nothing to stop a malicious or faulty accelerator from returning a
*wrong* ``y_b = (x_b @ W_q) mod p`` — Slalom pairs blinding with Freivalds'
check for exactly this reason, and DarKnight likewise couples its blinding
with integrity verification. This module is the enclave side of that check:

- **Fold vectors.** Per (session, layer, step) the enclave draws ``s``
  uniform over Z_p^(d_out × k) and precomputes ``ws = (W_q @ s) mod p``
  alongside the blinding factors (core/precompute.py) — O(d_in·d_out·k),
  off the request path, riding the same SessionPool prefetch ring.
- **Check.** For a device result ``y_b`` of the offloaded op
  ``x_b @ W_q``, the enclave verifies ``y_b @ s ≡ x_b @ ws (mod p)`` at
  O(t·(d_in+d_out)·k) instead of re-doing the O(t·d_in·d_out) matmul.
  The fused data path (DESIGN.md §6) never materializes ``y_b``; there the
  equivalent post-unblind identity ``y_q @ s ≡ x_q @ ws (mod p)`` is
  checked instead (the unblinding factor ``u = r @ W_q`` cancels exactly).
- **Soundness.** If the device returns ``y' ≠ y``, some row of
  ``y' − y`` is a nonzero vector ``d`` over Z_p, and
  ``P[d · s_col ≡ 0] = 1/p`` per independent fold column (p prime, s
  uniform); ``k`` columns give detection probability ``1 − p^-k``
  (p = 2^23 − 15: k=1 misses ~1.2e-7, k=2 ~1.4e-14).
- **Policy.** ``off`` (trust the device, the pre-PR-3 behavior),
  ``sampled(rate)`` (per-op Bernoulli decision drawn from the verify key —
  cheap spot-checking, but an *adaptive* adversary that corrupts only
  unverified ops evades it, see runtime/faults.py), ``full`` (every op).

Key separation: everything verification-related derives from
``fold_in(session_key, VERIFY_DOMAIN)`` so fold vectors and sampling
decisions are independent of the blinding streams (and, like them,
unpredictable to the device before it commits to a result).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Tuple

import jax
import jax.numpy as jnp

from repro.core import blinding as B
from repro.core import tracing
from repro.kernels.limb_matmul.ops import field_fold

# fold_in domain tags (distinct from any layer index / step used elsewhere)
VERIFY_DOMAIN = 0x5ECC
_SUB_FOLD = 0      # -> fold-vector draw
_SUB_DECIDE = 1    # -> sampled-mode check/skip decision
_SUB_SHARD = 2     # -> per-shard fold-vector draws (offload sharding)

MODES = ("off", "sampled", "full")


@dataclass(frozen=True)
class IntegrityPolicy:
    """Per-executor verification policy (static: part of the jit trace).

    ``mode``: "off" | "sampled" | "full"; ``rate``: per-op check
    probability under "sampled"; ``k``: independent Freivalds repetitions
    (soundness 1 − p^-k).
    """
    mode: str = "off"
    rate: float = 0.25
    k: int = 1

    def __post_init__(self):
        assert self.mode in MODES, self.mode
        assert self.k >= 1, self.k
        assert 0.0 <= self.rate <= 1.0, self.rate

    @property
    def enabled(self) -> bool:
        return self.mode != "off"

    @classmethod
    def off(cls) -> "IntegrityPolicy":
        return cls("off")

    @classmethod
    def full(cls, k: int = 1) -> "IntegrityPolicy":
        return cls("full", k=k)

    @classmethod
    def sampled(cls, rate: float = 0.25, k: int = 1) -> "IntegrityPolicy":
        return cls("sampled", rate=rate, k=k)


def verify_root(session_key: jax.Array) -> jax.Array:
    """Verification key domain, disjoint from the blinding-stream domain."""
    return jax.random.fold_in(session_key, VERIFY_DOMAIN)


def op_key(session_key: jax.Array, layer_id: int, step: int = 0) -> jax.Array:
    return B.stream_key(verify_root(session_key), layer_id, step)


def fold_stream(session_key: jax.Array, layer_id: int, step: int,
                d_out: int, k: int) -> jax.Array:
    """The fold vectors ``s``: (d_out, k) uniform field elements. Same
    derivation in the precompute cache and the on-the-fly trace, so cached
    and live verification are bit-identical."""
    key = jax.random.fold_in(op_key(session_key, layer_id, step), _SUB_FOLD)
    return B.blinding_stream(key, (d_out, k))


def shard_fold_stream(session_key: jax.Array, layer_id: int, step: int,
                      shard: int, d_out: int, k: int) -> jax.Array:
    """Per-shard fold vectors for the multi-device plane
    (parallel/offload_sharding.py): each shard of one offloaded matmul is
    checked with its OWN (d_out, k) draw, so a device can learn nothing
    about another shard's check from its retry/hedge traffic. Same
    derivation in core/precompute.py's prefetch ring and the live path —
    cached and live shard verification are bit-identical."""
    key = jax.random.fold_in(jax.random.fold_in(
        op_key(session_key, layer_id, step), _SUB_SHARD), shard)
    return B.blinding_stream(key, (d_out, k))


def decide(policy: IntegrityPolicy, session_key: jax.Array, layer_id: int,
           step: int = 0) -> jax.Array:
    """Traced per-op check/skip decision (scalar bool).

    "full" is a trace constant (no randomness, no cond); "sampled" draws a
    Bernoulli(rate) from the verify key so distinct (session, op, step)
    triples decide independently — and re-running a session re-decides
    identically (the schedule is a pure function of the key, which is what
    lets tests and the fault injector's adaptive adversary reason about it).
    """
    if policy.mode == "full":
        return jnp.bool_(True)
    if policy.mode == "off":
        return jnp.bool_(False)
    key = jax.random.fold_in(op_key(session_key, layer_id, step), _SUB_DECIDE)
    return jax.random.uniform(key) < policy.rate


def fold_check(y_field: jax.Array, x_field: jax.Array,
               s: jax.Array, ws: jax.Array) -> jax.Array:
    """Freivalds identity: ``y @ s ≡ x @ ws (mod p)`` — scalar bool.

    y_field: (t, d_out) field elements in [0, p) (the device's answer,
    blinded or unblinded form); x_field: (t, d_in) the matching operand the
    enclave holds; s: (d_out, k); ws: (d_in, k) = (W_q @ s) mod p.

    Evaluated as ONE fold: ``[y | x] @ [s; −ws] ≡ 0 (mod p)`` — same
    MAC count as the two-fold form but a single limb-decomposition and
    mod-recombination chain, which is what keeps the honest-path verify
    overhead inside the BENCH_integrity.json budget.
    """
    from repro.kernels.limb_matmul.ref import P
    yx = jnp.concatenate([y_field, x_field], axis=1)
    s_neg = jnp.concatenate([s, jnp.mod(P - ws, P)], axis=0)
    return jnp.all(field_fold(yx, s_neg) == 0)


def checked_pair(y_field: jax.Array, x_field: jax.Array, s: jax.Array,
                 ws: jax.Array, will_check: jax.Array,
                 always: bool) -> Tuple[jax.Array, jax.Array]:
    """Run the fold check under the policy decision.

    Returns (checked, failed) scalar bools. ``always`` (static) skips the
    lax.cond so "full" mode pays no branch; under "sampled" the cond means
    a skipped op costs zero fold work at runtime. Traced under the
    ``tier1.verify`` device phase.
    """
    with jax.named_scope(tracing.TIER1_VERIFY):
        if always:
            return jnp.bool_(True), ~fold_check(y_field, x_field, s, ws)
        ok = jax.lax.cond(will_check,
                          lambda: fold_check(y_field, x_field, s, ws),
                          lambda: jnp.bool_(True))
        return will_check, will_check & ~ok


@dataclass
class IntegrityReport:
    """Per-infer verification outcome: one slot per blinded op, in call
    order (empty arrays when the policy is off and no injector is
    installed). ``corrupted`` is the fault injector's ground truth —
    all-False on an honest device."""
    checked: jax.Array          # (n_ops,) bool — check actually ran
    failed: jax.Array           # (n_ops,) bool — check ran and mismatched
    corrupted: jax.Array        # (n_ops,) bool — injector changed the result

    @property
    def n_ops(self) -> int:
        return int(self.checked.shape[0])

    @property
    def n_checked(self) -> int:
        import numpy as np
        return int(np.asarray(self.checked).sum())

    @property
    def n_failed(self) -> int:
        import numpy as np
        return int(np.asarray(self.failed).sum())

    @property
    def n_corrupted(self) -> int:
        import numpy as np
        return int(np.asarray(self.corrupted).sum())

    @property
    def ok(self) -> bool:
        return self.n_failed == 0

    @classmethod
    def empty(cls) -> "IntegrityReport":
        z = jnp.zeros((0,), jnp.bool_)
        return cls(checked=z, failed=z, corrupted=z)
