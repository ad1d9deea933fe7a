"""Sealed channel + attestation simulation."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.configs import get_smoke
from repro.core.attestation import measure_enclave, verify_quote
from repro.core.sealing import (_authenticated_words, _mac, _unseal_core,
                                seal, unseal)
from repro.models import model as M


def _key(seed=7):
    return jax.random.key_data(jax.random.PRNGKey(seed))


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 2 ** 31 - 1))
def test_seal_roundtrip(seed):
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    box = seal(_key(), x, jnp.asarray([seed & 0xFFFF, 2], jnp.uint32))
    pt, ok = unseal(_key(), box, x.shape)
    assert bool(ok)
    np.testing.assert_array_equal(np.asarray(pt), np.asarray(x))


def test_tamper_detection(rng):
    x = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    box = seal(_key(), x, jnp.asarray([1, 2], jnp.uint32))
    bad = box._replace(ciphertext=box.ciphertext.at[0, 0].add(1))
    _, ok = unseal(_key(), bad, x.shape)
    assert not bool(ok)


def test_nonce_tamper_detected(rng):
    """The nonce selects the keystream, so it must be authenticated: a
    swapped nonce (e.g. another rid's split, or a stripped direction tag)
    must fail the MAC, not decrypt to garbage with ok=True."""
    x = jnp.asarray(rng.normal(size=(4, 8)), jnp.float32)
    box = seal(_key(), x, jnp.asarray([1, 2, 0xEE], jnp.uint32))
    swapped = box._replace(nonce=jnp.asarray([3, 2, 0xEE], jnp.uint32))
    _, ok = unseal(_key(), swapped, x.shape)
    assert not bool(ok)
    stripped = box._replace(nonce=box.nonce[:2])   # drop the direction tag
    _, ok = unseal(_key(), stripped, x.shape)
    assert not bool(ok)


def test_wrong_key_garbles(rng):
    x = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    box = seal(_key(1), x, jnp.asarray([1, 2], jnp.uint32))
    pt, ok = unseal(_key(2), box, x.shape)
    assert not bool(ok)
    assert not np.allclose(np.asarray(pt), np.asarray(x))


def test_nonce_changes_ciphertext(rng):
    x = jnp.asarray(rng.normal(size=(16,)), jnp.float32)
    b1 = seal(_key(), x, jnp.asarray([1, 0], jnp.uint32))
    b2 = seal(_key(), x, jnp.asarray([2, 0], jnp.uint32))
    assert not np.array_equal(np.asarray(b1.ciphertext),
                              np.asarray(b2.ciphertext))


def test_quote_stable_and_sensitive():
    cfg = get_smoke("vgg16")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    q1 = measure_enclave(cfg, params, 3)
    q2 = measure_enclave(cfg, params, 3)
    assert verify_quote(q1, q2)
    q3 = measure_enclave(cfg, params, 4)       # different partition
    assert not verify_quote(q1, q3)
    params2 = M.init_params(cfg, jax.random.PRNGKey(1))
    q4 = measure_enclave(cfg, params2, 3)      # different weights
    assert q4.measurement != q1.measurement


# --- the MAC's tag, held to Horner's recurrence -------------------------

MAC_LENGTHS = [1, 2, 3, 1004, 150531]   # 1004: a response; 150531: a request
MAC_SEEDS = [0, 7, 2 ** 31 - 1]


def _words(n):
    i = np.arange(n, dtype=np.uint64)
    return ((i * 2654435761 + 12345) % 2 ** 32).astype(np.uint32)


def _horner(key, words):
    """The tag by the sequential recurrence acc <- acc*c0 + w + c1 from
    the IV, one word at a time, in Python ints wrapped to 32 bits."""
    k = jax.random.fold_in(jax.random.wrap_key_data(
        jnp.asarray(key, jnp.uint32)), jnp.uint32(0xA11CE))
    c0, c1 = (int(c) for c in np.asarray(jax.random.bits(k, (2,),
                                                         jnp.uint32)))
    c0 |= 1
    acc = 0x9E3779B9
    for w in words.tolist():
        acc = (acc * c0 + w + c1) & 0xFFFFFFFF
    return acc


@pytest.mark.parametrize("n", MAC_LENGTHS)
def test_mac_matches_horner_recurrence(n):
    words = _words(n)
    for seed in MAC_SEEDS:
        got = int(jax.jit(_mac)(_key(seed), jnp.asarray(words)))
        assert got == _horner(_key(seed), words), (seed, n)


@pytest.mark.parametrize("n", MAC_LENGTHS)
def test_mac_vmapped_over_keys_matches_horner(n):
    keys = jnp.stack([_key(s) for s in MAC_SEEDS])
    words = np.stack([_words(n) ^ np.uint32(s) for s in range(len(keys))])
    got = np.asarray(jax.jit(jax.vmap(_mac))(keys, jnp.asarray(words)))
    want = [_horner(k, w) for k, w in zip(keys, words)]
    np.testing.assert_array_equal(got, np.asarray(want, np.uint32))


# tags taken from the sequential scan the closed form replaced; a drift in
# either the coefficients or the evaluation fails here
FROZEN_TAGS = [(0, 1004, 3436872847), (7, 150531, 568806020),
               (2 ** 31 - 1, 3, 4172035296)]


@pytest.mark.parametrize("seed,n,tag", FROZEN_TAGS)
def test_mac_frozen_tags(seed, n, tag):
    assert int(jax.jit(_mac)(_key(seed), jnp.asarray(_words(n)))) == tag


def test_box_sealed_by_the_scan_still_verifies():
    """A box whose tag was computed by the sequential scan opens under
    the closed form, and the closed form seals the same tag."""
    # made on the host: a device's float division need not round as the
    # host's does, and the frozen tag covers these exact bits
    x = jnp.asarray(np.arange(24, dtype=np.float32).reshape(4, 6)
                    / np.float32(7))
    box = seal(_key(), x, jnp.asarray([1, 2], jnp.uint32))
    assert int(box.mac) == 1574269141
    pt, ok = unseal(_key(), box, x.shape)
    assert ok
    np.testing.assert_array_equal(np.asarray(pt), np.asarray(x))


@pytest.mark.parametrize("where", ["first", "middle", "last", "nonce"])
def test_mac_changes_with_any_word(where):
    ct = jnp.asarray(_words(1004))
    nonce = jnp.asarray([5, 9, 0xEE], jnp.uint32)
    base = int(_mac(_key(), _authenticated_words(nonce, ct)))
    if where == "nonce":
        nonce = nonce.at[1].set(nonce[1] ^ 1)
    else:
        i = {"first": 0, "middle": ct.size // 2, "last": ct.size - 1}[where]
        ct = ct.at[i].set(ct[i] ^ 1)
    assert int(_mac(_key(), _authenticated_words(nonce, ct))) != base


def test_request_unseal_has_no_per_word_loop():
    """A 224x224x3 request's unseal lowers with no while loop over its
    150531 authenticated words (the keystream's threefry loops, rolled
    on the CPU and unrolled on the TPU, may remain)."""
    u32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.uint32)
    text = _unseal_core.lower(u32((2,)), u32((224, 224, 3)),
                              u32((2,))).as_text()
    assert "tensor<150531xui32>" in text       # the words are seen
    loops = [ln for ln in text.splitlines() if "stablehlo.while" in ln]
    assert not [ln for ln in loops if "tensor<150531xui32>" in ln]
