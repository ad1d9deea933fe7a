"""VGG-16, configuration D, served privately: its weights, its sealed image
pool, and its plain reference.

The reference imports nothing of the program. It states the configuration's
arithmetic directly: the first ``tier1_layers`` layers run in the blinding
field (activations quantized to ``k_act`` bits by their absmax over the
whole padded batch, weights to ``k_w`` bits by their absmax, an exact
integer product reduced mod p), the rest in float32 at ``highest``. Its
control computes every open layer's product in int8 (absmax per tensor),
the next precision below the bfloat16 products that the TPU runs the
program's float32 layers with by default.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from trace_reduce import vgg_forward_flops

HIGHEST = jax.lax.Precision.HIGHEST


def _layers(conf) -> List[Tuple[str, int]]:
    out = []
    for spec in conf["layers"]:
        if spec.startswith("conv"):
            out.append(("conv", int(spec[4:])))
        elif spec.startswith("fc"):
            out.append(("fc", int(spec[2:])))
        else:
            out.append((spec, conf["num_classes"] if spec == "logits" else 0))
    return out


def _weight_shapes(conf) -> Dict[str, Tuple[Tuple[int, ...], int]]:
    """name -> (weight shape, fan-in), in the program's parameter layout."""
    h = conf["image_size"]
    c, flat, out = conf["image_channels"], None, {}
    for i, (kind, n) in enumerate(_layers(conf)):
        if kind == "conv":
            out[f"l{i}"] = ((3, 3, c, n), 9 * c)
            c = n
        elif kind == "pool":
            h //= 2
        else:
            d_in = flat if flat is not None else h * h * c
            out[f"l{i}"] = ((d_in, n), d_in)
            flat = n
    return out


def make_params(conf):
    """He-normal weights and zero biases, made on the device in one call."""
    shapes = _weight_shapes(conf)

    def init(key):
        keys = jax.random.split(key, len(shapes))
        return {name: {"w": jax.random.normal(k, shp, jnp.float32)
                       * np.float32(np.sqrt(2.0 / fan_in)),
                       "b": jnp.zeros((shp[-1],), jnp.float32)}
                for k, (name, (shp, fan_in)) in zip(keys, shapes.items())}

    return jax.jit(init)(jax.random.PRNGKey(conf["assumed"]["weights_seed"]))


def field_ops(conf, bucket: int) -> List[Tuple[int, int, int]]:
    """(M, K, N) of each blinded tier-1 conv for a batch of ``bucket``:
    im2col rows x fan-in x output channels."""
    h = conf["image_size"]
    c, out = conf["image_channels"], []
    for i, (kind, n) in enumerate(_layers(conf)[:conf["tier1_layers"]]):
        if kind == "conv":
            out.append((bucket * h * h, 9 * c, n))
            c = n
        elif kind == "pool":
            h //= 2
    return out


# -- the plain reference -----------------------------------------------------

def _quantized_conv(conf, x, w, b):
    """One tier-1 conv as the configuration states it, exactly."""
    bl = conf["blinding"]
    p = bl["field_prime"]
    half = (p - 1) // 2
    xf = x.astype(jnp.float32)
    x_scale = jnp.maximum(jnp.max(jnp.abs(xf)), 1e-9)
    xq = jnp.clip(jnp.round(xf * (1.0 / x_scale) * 2.0 ** bl["k_act"]),
                  -half, half)
    w_scale = jnp.maximum(jnp.max(jnp.abs(w)), 1e-9)
    wq = jnp.clip(jnp.round(w / w_scale * 2.0 ** bl["k_w"]), -half, half)
    # integers below 2^9 times 2^8, summed over at most 4608 terms: exact
    # in float32 while partial sums stay under 2^24
    d = jax.lax.conv_general_dilated(
        xq, wq, (1, 1), "SAME", dimension_numbers=("NHWC", "HWIO", "NHWC"),
        precision=HIGHEST)
    di = jnp.mod(d.astype(jnp.int32), p)
    ys = jnp.where(di > half, di - p, di).astype(jnp.float32)
    y = ys * (x_scale * w_scale) * 2.0 ** -(bl["k_act"] + bl["k_w"])
    return y + b


def _int8(a):
    """(int8 values as float32, scale) by absmax over the whole tensor."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-9) / 127.0
    return jnp.round(a / s), s


def reference_logits(conf, params, images, control=False):
    """Logits of a padded batch; ``control`` takes every open product in
    int8."""
    x = images.astype(jnp.float32)
    for i, (kind, n) in enumerate(_layers(conf)):
        p = params.get(f"l{i}")
        if kind == "conv":
            if i < conf["tier1_layers"]:
                y = _quantized_conv(conf, x, p["w"], p["b"])
            else:
                (xq, sx), (wq, sw) = ((_int8(x), _int8(p["w"])) if control
                                      else ((x, 1.0), (p["w"], 1.0)))
                y = jax.lax.conv_general_dilated(
                    xq, wq, (1, 1), "SAME",
                    dimension_numbers=("NHWC", "HWIO", "NHWC"),
                    precision=HIGHEST) * (sx * sw) + p["b"]
            x = jax.nn.relu(y)
        elif kind == "pool":
            x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max,
                                      (1, 2, 2, 1), (1, 2, 2, 1), "VALID")
        else:
            x = x.reshape(x.shape[0], -1)
            (xq, sx), (wq, sw) = ((_int8(x), _int8(p["w"])) if control
                                  else ((x, 1.0), (p["w"], 1.0)))
            y = jnp.dot(xq, wq, precision=HIGHEST) * (sx * sw) + p["b"]
            x = jax.nn.relu(y) if kind == "fc" else y
    return x


def logit_err(served: np.ndarray, ref: np.ndarray) -> float:
    """Widest gap of a row's logits from the reference's, as a share of
    the reference row's largest magnitude."""
    gap = np.max(np.abs(served - ref), axis=1)
    return float(np.max(gap / np.max(np.abs(ref), axis=1)))


class Model:
    """The system under test for this configuration, and its yardstick."""

    unit = "images"

    def __init__(self, conf: Dict[str, Any], traffic: Dict[str, Any]):
        self.conf = conf
        self.name = conf["name"]
        self.params = make_params(conf)
        self.flops_per_unit = vgg_forward_flops(
            conf["layers"], conf["image_size"], conf["image_channels"],
            conf["num_classes"])
        self._ref = {ctl: jax.jit(functools.partial(reference_logits, conf,
                                                    control=ctl))
                     for ctl in (False, True)}

    # -- the program ----------------------------------------------------------
    def program_config(self):
        """The program's config for this file's sizes; raises where the
        program cannot run what the file states."""
        from repro.configs import get_config
        from repro.configs.base import OrigamiConfig
        from repro.core.blinding import BlindingSpec
        from repro.kernels.limb_matmul.ref import P
        c, bl = self.conf, self.conf["blinding"]
        spec = BlindingSpec()
        if (spec.k_act, spec.k_w, P) != (bl["k_act"], bl["k_w"],
                                         bl["field_prime"]):
            raise SystemExit(f"{self.name}: the program blinds with "
                             f"{(spec.k_act, spec.k_w, P)}, not {bl}")
        return get_config(self.name).replace(
            cnn_layers=tuple(c["layers"]), num_layers=len(c["layers"]),
            image_size=c["image_size"], image_channels=c["image_channels"],
            num_classes=c["num_classes"], dtype=c["dtype"],
            origami=OrigamiConfig(enabled=True,
                                  tier1_layers=c["tier1_layers"]))

    def published(self) -> bool:
        """Whether the program's own config is the one this file states."""
        from repro.configs import get_config
        return self.program_config() == get_config(self.name)

    def register(self, engine) -> str:
        from repro.core.integrity import IntegrityPolicy
        integ = self.conf["integrity"]
        if integ["mode"] != "full":
            raise ValueError(integ)
        engine.register_model(self.name, self.program_config(), self.params,
                              integrity=IntegrityPolicy.full(k=integ["k"]))
        return self.name

    def make_pool(self, n: int, seed_key, rng: np.random.Generator):
        """``n`` sealed requests: ``[(Request, image)]``."""
        from repro.core.sealing import SealedBox, _seal_core
        from repro.runtime.serving import Request, request_nonce
        c = self.conf
        shape = (c["image_size"], c["image_size"], c["image_channels"])
        images = jax.jit(lambda k: jax.random.uniform(
            k, (n,) + shape, jnp.float32))(seed_key)
        keys = rng.integers(0, 2 ** 32 - 1, size=(n, 2), dtype=np.uint32)
        nonces = np.stack([np.asarray(request_nonce(i)) for i in range(n)])
        ct, mac = jax.jit(jax.vmap(_seal_core))(keys, images, nonces)
        ct, mac, images = np.asarray(ct), np.asarray(mac), np.asarray(images)
        return [(Request(rid=i, box=SealedBox(ct[i], nonces[i], mac[i]),
                         shape=shape, session_key=keys[i]), images[i])
                for i in range(n)]

    def open(self, item, resp) -> np.ndarray:
        from repro.runtime.serving import PrivateInferenceServer
        req = item[0]
        return PrivateInferenceServer.client_open(
            req.session_key, resp.box, (self.conf["num_classes"],))

    def units(self, output) -> int:
        return 1

    # -- the roofline's work ---------------------------------------------------
    def field_work(self, delta: Dict[str, float], buckets: Sequence[int]):
        """[(kind, M, K, N, count)] of every field matmul the window ran,
        from the program's counters: the fused blinded matmuls of each
        batch; the u = r·W_q and W_q·s products of each session the pool
        prefetched (into every bucket's cache), and of each session it
        missed (made for the batch's own bucket, here shared out over the
        buckets in proportion to their batches)."""
        k = self.conf["integrity"]["k"]
        batches = {b: delta.get(f"engine.bucket.{b}.batches", 0)
                   for b in buckets}
        total = sum(batches.values())
        refilled = delta.get("pool.refilled", 0)
        missed = delta.get("pool.misses", 0)
        out = []
        for b in buckets:
            sessions = refilled + (missed * batches[b] / total if total
                                   else 0)
            for M, K, N in field_ops(self.conf, b):
                out += [("fused", M, K, N, batches[b]),
                        ("plain", M, K, N, sessions),
                        ("plain", K, N, k, sessions)]
        return out

    # -- the comparison that decides correct ---------------------------------
    def reference_readings(self, batches, control: bool = False):
        """``batches``: [(bucket, [(image, served logits)])]. Returns the
        compared numbers of the served outputs (or, with ``control``, of
        the int8 control put in their place)."""
        ref, ctl = self._ref[False], self._ref[True]
        worst = 0.0
        for bucket, rows in batches:
            imgs = np.stack([img for img, _ in rows])
            pad = np.zeros((bucket - len(rows),) + imgs.shape[1:], imgs.dtype)
            x = jnp.asarray(np.concatenate([imgs, pad]))
            want = np.asarray(ref(self.params, x))[:len(rows)]
            got = (np.asarray(ctl(self.params, x))[:len(rows)] if control
                   else np.stack([out for _, out in rows]))
            worst = max(worst, logit_err(got, want))
        return {"logit_err": worst}

