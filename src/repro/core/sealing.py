"""Encrypted request channel: counter-mode stream cipher + keyed MAC.

Models the user->enclave path (paper Fig. 3a: the user encrypts the input;
only the enclave can decrypt). We use a threefry-based CTR stream cipher
over float bit-patterns plus a polynomial MAC — *not* production AES-GCM,
but a faithful functional stand-in with the same interface and the same
cost shape (one pass to decrypt, one to authenticate), suitable for the
serving pipeline and its tests.

MAC verification compares canonical byte encodings with
``hmac.compare_digest`` — a data-dependent early-exit ``==`` would hand a
network attacker a timing oracle over the tag (and the jnp comparison it
replaced also forced a device sync per word).

The cipher/MAC arithmetic is jitted (``_seal_core`` / ``_unseal_core``):
the keystream derivation and the MAC are pure integer ops. The MAC's
polynomial is evaluated in closed form, data-parallel over the words (one
power per word, one sum), not by Horner's rule one word per loop trip: a
serial loop pays a fixed cost per trip on a TPU (about 1.4 us on a v5e,
0.2 s for a 224x224x3 request). The tag is bit-identical to Horner's
(see ``_mac``).
Only the trust-boundary tag compare stays on the host (a Python bool from
``hmac.compare_digest``, constant-time), so the security posture is
unchanged. Each core runs under its device phase (core/tracing.py
``DEVICE_PHASES``).
"""
from __future__ import annotations

import hmac
from typing import NamedTuple, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import tracing


class SealedBox(NamedTuple):
    ciphertext: jax.Array      # uint32 bit-patterns
    nonce: jax.Array           # (>=2,) uint32 (word 2+: e.g. direction tag)
    mac: jax.Array             # () uint32


def _keystream(key: jax.Array, nonce: jax.Array, n: int) -> jax.Array:
    """Nonce words fold in sequentially, so nonces of different lengths
    live in disjoint key domains (a request's (lo, hi) can never collide
    with a response's (lo, hi, tag))."""
    k = jax.random.wrap_key_data(jnp.asarray(key, jnp.uint32))
    for i in range(nonce.shape[0]):
        k = jax.random.fold_in(k, nonce[i])
    return jax.random.bits(k, (n,), jnp.uint32)


def _mac(key: jax.Array, data_u32: jax.Array) -> jax.Array:
    """Carter-Wegman-style polynomial MAC over u32 words (mod 2^32).

    The tag is the value of Horner's recurrence ``acc <- acc*c0 + w_i + c1``
    from ``acc = IV`` over the N words, written in closed form:

        tag = c0^N * IV + sum_{i<N} (w_i + c1) * c0^(N-1-i)   (mod 2^32)

    uint32 multiply and add wrap mod 2^32, and sums in that ring do not
    depend on the order of reduction, so this equals the recurrence bit
    for bit. The powers come from square-and-multiply over the exponents
    N-1-i, ceil(log2 N) elementwise steps with no loop over the words.
    """
    k = jax.random.fold_in(jax.random.wrap_key_data(
        jnp.asarray(key, jnp.uint32)), jnp.uint32(0xA11CE))
    coeff = jax.random.bits(k, (2,), jnp.uint32)
    c0 = coeff[0] | jnp.uint32(1)      # odd => unit mod 2^32 (invertible)

    n = data_u32.shape[0]
    exps = jnp.arange(n - 1, -1, -1, dtype=jnp.uint32)   # N-1-i
    pows = jnp.ones((n,), jnp.uint32)
    base = c0
    for b in range((n - 1).bit_length()):
        pows = jnp.where((exps >> b) & 1, pows * base, pows)
        base = base * base
    # pows[0] is c0^(N-1)
    return (pows[0] * c0 * jnp.uint32(0x9E3779B9)
            + jnp.sum((data_u32 + coeff[1]) * pows, dtype=jnp.uint32))


def _authenticated_words(nonce: jax.Array, ct: jax.Array) -> jax.Array:
    """MAC input: length-prefixed nonce || ciphertext. The nonce selects
    the keystream, so it MUST be authenticated — an unauthenticated nonce
    swap would pass verification and decrypt to attacker-chosen garbage.
    The length prefix keeps (nonce, ct) framings of different nonce widths
    (request 2-word vs. response 3-word) from aliasing."""
    n = jnp.asarray(nonce, jnp.uint32).reshape(-1)
    return jnp.concatenate([jnp.asarray([n.size], jnp.uint32), n, ct])


@jax.jit
def _seal_core(key: jax.Array, x: jax.Array,
               nonce: jax.Array) -> Tuple[jax.Array, jax.Array]:
    with jax.named_scope(tracing.ENCLAVE_SEAL):
        bits = jax.lax.bitcast_convert_type(
            x.astype(jnp.float32), jnp.uint32).reshape(-1)
        ks = _keystream(key, nonce, bits.size)
        ct = bits ^ ks
        return (ct.reshape(x.shape),
                _mac(key, _authenticated_words(nonce, ct)))


def _unseal_words(key: jax.Array, ct: jax.Array,
                  nonce: jax.Array) -> Tuple[jax.Array, jax.Array]:
    ct_flat = ct.reshape(-1)
    want = _mac(key, _authenticated_words(nonce, ct_flat))
    ks = _keystream(key, nonce, ct_flat.size)
    return (jax.lax.bitcast_convert_type(ct_flat ^ ks,
                                         jnp.float32).reshape(ct.shape),
            want)


# one body, two programs: the enclave's unseal of a request and the
# client's open of a response run the same words, MAC and compare, each
# traced under its own device phase
@jax.jit
def _unseal_core(key: jax.Array, ct: jax.Array,
                 nonce: jax.Array) -> Tuple[jax.Array, jax.Array]:
    with jax.named_scope(tracing.ENCLAVE_UNSEAL):
        return _unseal_words(key, ct, nonce)


@jax.jit
def _open_core(key: jax.Array, ct: jax.Array,
               nonce: jax.Array) -> Tuple[jax.Array, jax.Array]:
    with jax.named_scope(tracing.CLIENT_OPEN):
        return _unseal_words(key, ct, nonce)


def seal(key: jax.Array, x: jax.Array, nonce: jax.Array) -> SealedBox:
    """Encrypt + authenticate a float tensor under the session key."""
    ct, mac = _seal_core(key, jnp.asarray(x), jnp.asarray(nonce, jnp.uint32))
    return SealedBox(ciphertext=ct, nonce=nonce, mac=mac)


def _open_box(core, key: jax.Array, box: SealedBox,
              shape: Tuple[int, ...]) -> Tuple[jax.Array, bool]:
    pt, want = core(jnp.asarray(key), jnp.asarray(box.ciphertext),
                    jnp.asarray(box.nonce, jnp.uint32))
    ok = hmac.compare_digest(
        np.asarray(want, np.uint32).tobytes(),
        np.asarray(box.mac, np.uint32).tobytes())
    return pt.reshape(shape), ok


def unseal(key: jax.Array, box: SealedBox,
           shape: Tuple[int, ...]) -> Tuple[jax.Array, bool]:
    """Returns (plaintext, mac_ok). Enclave-side.

    ``mac_ok`` is a Python bool from a constant-time compare over the
    canonical little-endian uint32 encodings of the two tags — the accept
    decision itself is never traced; only the tag/keystream arithmetic is.
    """
    return _open_box(_unseal_core, key, box, shape)


def open_response(key: jax.Array, box: SealedBox,
                  shape: Tuple[int, ...]) -> Tuple[jax.Array, bool]:
    """``unseal`` for the client opening a response: the same words, MAC
    and compare, run as the client's own program (device phase
    ``client.open``)."""
    return _open_box(_open_core, key, box, shape)
