"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: each kernel is lowered and compiled by the TPU compiler for a
``v5e:2x2`` topology that is described, not attached, at the block plan
``kernels/limb_matmul/ops.py`` picks for VGG-16 tier-1 and smollm-135m
decode shapes. A pass shows that Mosaic accepts the kernel (a
``tpu_custom_call`` in the compiled HLO) and that the program fits one
chip's 16 GB of HBM. The topology is described inside a fixture, never at
import time, so every test worker collects the same tests and only the
worker running this file loads the TPU compiler.
"""
import functools
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.blind.blind import blind_encode_pallas
from repro.kernels.limb_matmul import ops
from repro.kernels.limb_matmul.fold import FOLD_LANES, limb_fold_planes
from repro.kernels.limb_matmul.limb_matmul import (limb_matmul_planes,
                                                  limb_matmul_planes_fused)

HBM_BYTES = 16 * 10 ** 9

# (M, K, N): VGG-16 conv1_2 / conv2_2 at 224x224, smollm-135m decode MLP
# projections, and a square 2k matmul
SHAPES = [(50176, 576, 64), (12544, 1152, 128), (8, 576, 1536),
          (4, 1536, 576), (256, 2048, 2048)]


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip can be written to the persistent
    # cache but never read back without the chip: keep the cache out
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)


def _sds(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _kernel_call(kernel, M, K, N, sharding):
    """(jitted kernel, abstract operands) at the padded block plan."""
    bm, bn, bk, Mp, Kp, Np = ops.block_plan(M, K, N)
    a = functools.partial(_sds, sharding)
    if kernel == "limb_matmul_planes":
        fn = functools.partial(limb_matmul_planes, bm=bm, bn=bn, bk=bk)
        args = (a((3, Mp, Kp), jnp.int8), a((3, Kp, Np), jnp.int8))
    elif kernel == "limb_matmul_planes_fused":
        fn = functools.partial(limb_matmul_planes_fused, bm=bm, bn=bn, bk=bk)
        args = (a((3, Mp, Kp), jnp.int8), a((3, Kp, Np), jnp.int8),
                a((Mp, Np), jnp.int32), a((1, 1), jnp.float32))
    elif kernel == "limb_fold_planes":
        bm, _, bk, Mp, Kp, _ = ops.block_plan(M, K, FOLD_LANES)
        fn = functools.partial(limb_fold_planes, bm=bm, bk=bk)
        args = (a((3, Mp, Kp), jnp.int8), a((3, Kp, FOLD_LANES), jnp.int8))
    else:
        fn = functools.partial(blind_encode_pallas, k_bits=8, bm=bm, bk=bk)
        args = (a((Mp, Kp), jnp.float32), a((Mp, Kp), jnp.int32),
                a((1, 1), jnp.float32))
    return jax.jit(fn), args


@pytest.mark.parametrize("M,K,N", SHAPES)
@pytest.mark.parametrize("kernel", ["limb_matmul_planes",
                                    "limb_matmul_planes_fused",
                                    "limb_fold_planes",
                                    "blind_encode_pallas"])
def test_kernel_compiles_for_v5e(one_chip, kernel, M, K, N):
    fn, args = _kernel_call(kernel, M, K, N, one_chip)
    compiled = fn.lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < HBM_BYTES, used


def test_kernels_keep_their_program_names_under_device_phases(one_chip):
    """A device phase scope at the call site rides in each kernel's
    ``op_name`` and leaves the kernel's instruction name in the compiled
    HLO (what the device trace names it by) that of its jitted program."""
    import re

    from repro.core import tracing
    M, K, N = 512, 576, 128
    _, _, _, _, Kp, Np = ops.block_plan(M, K, N)
    a = functools.partial(_sds, one_chip)

    def tier1(x, r, w_limbs, u, w_field):
        with jax.named_scope(tracing.TIER1_MATMUL):
            y = ops.fused_blinded_matmul(x, r, w_limbs, u, 1.0, 1.0,
                                         k_bits=8, k_out_bits=16,
                                         impl="pallas")
        with jax.named_scope(tracing.FACTORS_UNBLIND):
            return y, ops.field_matmul(r, w_field, impl="pallas")

    text = jax.jit(tier1).lower(
        a((M, K), jnp.float32), a((M, K), jnp.int32),
        a((3, Kp, Np), jnp.int8), a((M, N), jnp.int32),
        a((K, N), jnp.int32)).compile().as_text()
    calls = [(m.group(1), m.group(2)) for m in re.finditer(
        r"%([\w.\-]+) = [^\n]*tpu_custom_call[^\n]*op_name=\"([^\"]*)\"",
        text)]
    names = sorted(n.rsplit(".", 1)[0] for n, _ in calls)
    assert names == ["_field_matmul_jit", "_fused_blinded_matmul_jit",
                     "_fused_blinded_matmul_jit"], calls
    for name, op_name in calls:
        phase = (tracing.FACTORS_UNBLIND if name.startswith("_field")
                 else tracing.TIER1_MATMUL)
        assert f"/{phase}/" in op_name, (name, op_name)
