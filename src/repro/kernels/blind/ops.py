"""Jitted public wrappers for blind/unblind with backend selection."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.blind import ref
from repro.kernels.blind.blind import blind_pallas, unblind_pallas


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@functools.partial(jax.jit, static_argnames=("k_bits", "impl"))
def _blind_jit(x, r, k_bits: int, impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu() and x.size < 2 ** 16):
        return ref.blind_ref(x, r, k_bits)
    return blind_pallas(x, r, k_bits,
                        interpret=(impl == "interpret")
                        or (impl == "auto" and not _on_tpu()))


@functools.partial(jax.jit, static_argnames=("k_out_bits", "out_dtype",
                                             "impl"))
def _unblind_jit(y, u, k_out_bits: int, out_dtype=jnp.float32,
                 impl: str = "auto"):
    if impl == "ref" or (impl == "auto" and not _on_tpu() and y.size < 2 ** 16):
        return ref.unblind_ref(y, u, k_out_bits, out_dtype)
    return unblind_pallas(y, u, k_out_bits, out_dtype,
                          interpret=(impl == "interpret")
                          or (impl == "auto" and not _on_tpu()))


def blind(x, r, k_bits: int, impl: str = "auto"):
    """(quantize(x) + r) mod p, the blinded operand the device sees."""
    return _blind_jit(x, r, k_bits=k_bits, impl=impl)


def unblind(y, u, k_out_bits: int, out_dtype=jnp.float32,
            impl: str = "auto"):
    """dequantize((y - u) mod p), the enclave's unblind."""
    return _unblind_jit(y, u, k_out_bits=k_out_bits, out_dtype=out_dtype,
                        impl=impl)
