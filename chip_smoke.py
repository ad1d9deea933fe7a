#!/usr/bin/env python3
"""Chip smoke test: the private-serving main path on a TPU, end to end.

Usage, on a host with a TPU:

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # the sharded offload plane, 4 chips

One chip runs three phases, and any failure in any of them raises:

1. kernel parity: the compiled Pallas field matmul, fused blinded matmul
   and Freivalds fold against their jnp references, bit for bit, at the
   VGG-16 tier-1 and smollm-135m decode shapes;
2. private VGG-16 serving at 224x224 through ``ServingEngine``: 8 sealed
   requests, every offloaded matmul Freivalds-checked, each opened
   response bit-identical to a separate enclave-recompute oracle;
3. private smollm-135m decode at its published widths: tokens and logits
   bit-identical to the enclave-recompute (``trusted=True``) stream.

``--four-chip`` runs only the sharded plane: VGG-16 tier-1 matmuls
row-sharded across every device of the host (``DevicePool.from_jax``),
compared bit for bit with a single-device executor.

Weights are random, made from ``--seed``. The script stops before any work
unless JAX's first device is a TPU. Its last line on stdout is one JSON
object: {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from importlib import metadata
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.runtime.aot import use_persistent_compile_cache  # noqa: E402

N_REQUESTS = 8
MAX_BATCH = 4
# (M, K, N): VGG-16 conv1_2 and conv2_2 at 224x224 (im2col rows x fan-in x
# channels), and the smollm-135m decode MLP up-projection at batch 8
PARITY_SHAPES = ((50176, 576, 64), (12544, 1152, 128), (8, 576, 1536))


def log(msg: str) -> None:
    print(msg, flush=True)


def peak_bytes() -> int:
    return int(jax.devices()[0].memory_stats()["peak_bytes_in_use"])


def require(cond, what: str) -> None:
    if not cond:
        raise AssertionError(what)


def check_kernel(jitted, what: str, *args, **kw) -> None:
    """The ``auto`` call compiles to a Pallas kernel, and that compiled
    program's output equals ``impl="ref"`` bit for bit."""
    compiled = jitted.lower(*args, **kw).compile()
    require("tpu_custom_call" in compiled.as_text(),
            f"{what}: compiled HLO holds no Pallas kernel")
    require(np.array_equal(compiled(*args), jitted(*args, impl="ref", **kw)),
            f"{what}: kernel != reference")


def kernel_parity(seed: int) -> None:
    """Compiled kernels (``impl="auto"`` on TPU) == jnp references."""
    from repro.core.blinding import BlindingSpec, quantize_weight
    from repro.kernels.limb_matmul import ops
    from repro.kernels.limb_matmul.ref import P

    spec = BlindingSpec()
    k_out = spec.k_act + spec.k_w
    key = jax.random.PRNGKey(seed)
    for M, K, N in PARITY_SHAPES:
        key, kx, kw, kr, ks, ka = jax.random.split(key, 6)
        x = jax.random.randint(kx, (M, K), 0, P, jnp.int32)
        w = jax.random.randint(kw, (K, N), 0, P, jnp.int32)
        s = jax.random.randint(ks, (K, 2), 0, P, jnp.int32)
        check_kernel(ops._field_matmul_jit, f"field_matmul {M}x{K}x{N}", x, w)
        check_kernel(ops._field_fold_jit, f"field_fold {M}x{K}x2", x, s)

        act = jax.random.normal(ka, (M, K), jnp.float32)
        w_q, w_scale = quantize_weight(
            jax.random.normal(kw, (K, N), jnp.float32), spec)
        r = jax.random.randint(kr, (M, K), 0, P, jnp.int32)
        u = ops.field_matmul(r, w_q, impl="ref")
        x_scale = jnp.max(jnp.abs(act))
        check_kernel(ops._fused_blinded_matmul_jit,
                     f"fused_blinded_matmul {M}x{K}x{N}",
                     act, r, ops.encode_weight_planes(w_q), u, 1.0 / x_scale,
                     x_scale * w_scale * 2.0 ** -k_out,
                     k_bits=spec.k_act, k_out_bits=k_out)
        log(f"[parity] {M}x{K}x{N}: field_matmul, fused_blinded_matmul, "
            f"field_fold compiled to tpu_custom_call and bit-identical to "
            f"the reference")


def sealed_images(cfg, n: int):
    """``n`` sealed requests, their client keys and the plain images."""
    from repro.launch.serve import _sealed_requests
    from repro.privacy.data import make_batch
    reqs, keys = _sealed_requests(cfg, n)
    images = np.concatenate([make_batch(r.rid, 1, cfg.image_size)
                             for r in reqs])
    return reqs, keys, images


def serve_vgg16(cfg, params, policy, *, devices=None):
    """Serve N_REQUESTS sealed requests through a ServingEngine. Returns
    (opened logits in request order, plain images, stats snapshot,
    register seconds). ``max_wait_ms`` is long enough that the engine
    forms exactly the batches of MAX_BATCH the oracle replays."""
    from repro.runtime.engine import EngineConfig, ServingEngine
    from repro.runtime.serving import PrivateInferenceServer

    engine = ServingEngine(EngineConfig(max_batch=MAX_BATCH, aot_warm=True,
                                        max_wait_ms=600_000.0))
    try:
        t0 = time.perf_counter()
        engine.register_model("vgg16", cfg, params, integrity=policy,
                              devices=devices, shard="rows")
        register_s = time.perf_counter() - t0
        reqs, keys, images = sealed_images(cfg, N_REQUESTS)
        futures = [engine.submit("vgg16", r) for r in reqs]
        responses = [f.result(timeout=900) for f in futures]
        stats = engine.stats.snapshot(engine)
    finally:
        engine.close()
    for resp in responses:
        require(resp.ok, f"request {resp.rid} failed: {resp.error}")
        require(not resp.flagged, f"request {resp.rid} flagged")
    logits = np.stack([
        PrivateInferenceServer.client_open(k, resp.box, (cfg.num_classes,))
        for k, resp in zip(keys, responses)])
    return logits, images, stats, register_s


def check_counters(stats) -> None:
    aot, integ = stats["aot"], stats["integrity"]
    for name, value in (("aot.disk_errors", aot["disk_errors"]),
                        ("aot.request_compile_seconds",
                         aot["request_compile_seconds"]),
                        ("refill_errors", stats["refill_errors"]),
                        ("verify_failures", integ["verify_failures"]),
                        ("recomputes", integ["recomputes"]),
                        ("trusted_batches", integ["trusted_batches"]),
                        ("degradations", stats["liveness"]["degradations"]),
                        ("rejected", stats["rejected"])):
        require(value == 0, f"{name} = {value}, expected 0")
    require(integ["verify_checks"] > 0, "no Freivalds check ran")
    require(stats["completed"] == N_REQUESTS, stats["completed"])


def phase_serving(seed: int) -> None:
    from repro.configs import get_config
    from repro.core.integrity import IntegrityPolicy
    from repro.core.origami import OrigamiExecutor
    from repro.models import model as M

    cfg = get_config("vgg16")
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    policy = IntegrityPolicy.full()
    logits, images, stats, register_s = serve_vgg16(cfg, params, policy)
    check_counters(stats)
    device_matmuls = stats["matmuls"]["vgg16"]["device"]
    require(device_matmuls > 0, "no matmul was offloaded")
    log(f"[serve] vgg16 {cfg.image_size}x{cfg.image_size} "
        f"tier1_layers={cfg.origami.tier1_layers}: "
        f"register+warm {register_s:.1f} s, cold compile "
        f"{stats['aot']['compile_seconds']:.1f} s over "
        f"{stats['aot']['compiles']} executables, ttfb_warm_s "
        f"{stats['ttfb_warm_s']:.3f}, peak_bytes_in_use {peak_bytes()}")

    # the oracle: a separate executor's enclave recompute over the same
    # batches the engine formed
    oracle = OrigamiExecutor(cfg, params, integrity=policy)
    for lo in range(0, N_REQUESTS, MAX_BATCH):
        batch = {"images": jnp.asarray(images[lo:lo + MAX_BATCH])}
        want = np.asarray(oracle.infer(batch, trusted=True).logits)
        require(np.array_equal(logits[lo:lo + MAX_BATCH], want),
                f"requests {lo}..{lo + MAX_BATCH - 1}: served logits differ "
                f"from the enclave-recompute oracle")
    require(np.isfinite(logits).all(), "non-finite logits")
    log(f"[serve] {N_REQUESTS}/{N_REQUESTS} responses bit-identical to the "
        f"oracle; device_matmuls {device_matmuls}, verify_checks "
        f"{stats['integrity']['verify_checks']}, sessions "
        f"{stats['sessions']['vgg16']}")


def phase_decode(seed: int) -> None:
    from repro.configs import get_config
    from repro.core.integrity import IntegrityPolicy
    from repro.models import model as M
    from repro.runtime.generate import private_generate

    cfg = get_config("smollm_135m")
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    prompt = jax.random.randint(jax.random.PRNGKey(seed + 1), (2, 16), 0,
                                cfg.vocab_size)
    kw = dict(max_new_tokens=8, integrity=IntegrityPolicy.full(k=2),
              session_key=jax.random.PRNGKey(seed + 9))
    t0 = time.perf_counter()
    priv = private_generate(params, prompt, cfg, **kw)
    priv_s = time.perf_counter() - t0
    oracle = private_generate(params, prompt, cfg, trusted=True, **kw)
    require(np.array_equal(priv.tokens, oracle.tokens),
            "private tokens differ from the trusted stream")
    require(np.array_equal(priv.logits, oracle.logits),
            "private logits differ from the trusted stream")
    require(np.isfinite(np.asarray(priv.logits)).all(), "non-finite logits")
    require(priv.telemetry.device_matmuls > 0, "no matmul was offloaded")
    require(priv.integrity.ok
            and priv.integrity.n_checked == priv.integrity.n_ops,
            "not every offloaded op passed its Freivalds check")
    require(priv.ring["consumed"] == priv.decode_steps, priv.ring)
    require(priv.ring["refill_errors"] == 0, priv.ring)
    log(f"[decode] smollm-135m L={cfg.num_layers} d={cfg.d_model} "
        f"vocab={cfg.vocab_size} batch 2, prompt 16, 8 new tokens: tokens "
        f"and logits bit-identical to trusted; "
        f"{int(priv.integrity.n_checked)} ops verified, ring {priv.ring}, "
        f"first private stream {priv_s:.1f} s (compile included), "
        f"peak_bytes_in_use {peak_bytes()}")


def phase_four_chip(seed: int) -> None:
    from repro.configs import get_config
    from repro.core.integrity import IntegrityPolicy
    from repro.core.origami import OrigamiExecutor
    from repro.models import model as M
    from repro.runtime.devices import DevicePool

    cfg = get_config("vgg16")
    params = M.init_params(cfg, jax.random.PRNGKey(seed))
    policy = IntegrityPolicy.full()
    pool = DevicePool.from_jax()
    require(pool.size == 4, f"expected 4 devices, found {pool.size}")
    logits, images, stats, register_s = serve_vgg16(cfg, params, policy,
                                                    devices=pool)
    check_counters(stats)
    integ = stats["integrity"]
    require(integ["shard_checks"] > 0, "no shard-local check ran")
    require(integ["shard_failures"] == 0 and integ["shard_enclave"] == 0,
            integ)
    slots = stats["devices"]["vgg16"]["pool"]["slots"]
    for s in slots:
        require(s["dispatches"] > 0, f"{s['name']} received no dispatch")
        require(not s["quarantined"] and s["breaker_opens"] == 0,
                f"{s['name']} was quarantined or breaker-opened: {s}")
    # the oracle: one device, the same eager interpreter the pooled
    # executor runs, over the same batches
    oracle = OrigamiExecutor(cfg, params, integrity=policy)
    for lo in range(0, N_REQUESTS, MAX_BATCH):
        batch = {"images": jnp.asarray(images[lo:lo + MAX_BATCH])}
        want = np.asarray(oracle.infer(batch, jit=False).logits)
        require(np.array_equal(logits[lo:lo + MAX_BATCH], want),
                f"requests {lo}..{lo + MAX_BATCH - 1}: sharded logits "
                f"differ from the single-device executor")
    log(f"[four-chip] {N_REQUESTS}/{N_REQUESTS} responses bit-identical to "
        f"the single-device executor; shard_checks {integ['shard_checks']}, "
        f"hedges {integ['shard_hedges']}, dispatches per device "
        f"{[s['dispatches'] for s in slots]}, register {register_s:.1f} s")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded offload plane on 4 chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    cache_dir = use_persistent_compile_cache()
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX's first device is "
              f"{dev.platform}", file=sys.stderr)
        return 1
    log(f"[device] platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)} jax={jax.__version__} "
        f"jaxlib={metadata.version('jaxlib')} "
        f"libtpu={metadata.version('libtpu')} compile_cache={cache_dir}")

    phases = ([("four-chip", phase_four_chip)] if args.four_chip else
              [("parity", kernel_parity), ("serving", phase_serving),
               ("decode", phase_decode)])
    for name, phase in phases:
        t0 = time.perf_counter()
        phase(args.seed)
        gc.collect()
        log(f"[{name}] passed in {time.perf_counter() - t0:.1f} s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
