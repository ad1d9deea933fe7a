#!/usr/bin/env bash
# Tier-1 CPU verification — the exact command ROADMAP.md names.
# Off the TPU the kernel wrappers take their bit-identical jnp
# references; kernel parity tests run Pallas with interpret=True.
set -euo pipefail
cd "$(dirname "$0")/.."
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python -m pytest -x -q "$@"
# serving-engine smoke: mixed vgg16/vgg19 through the async engine,
# logits cross-checked bit-exactly against the legacy synchronous server
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.serve --smoke --engine
# integrity smoke: sampled Freivalds policy at rate 1.0 with a dishonest
# device flipping bits — the drill fails unless every corruption is
# detected, recovered (responses stay bit-exact vs the honest legacy
# server) and the backend quarantined (DESIGN.md §9)
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.serve --smoke --engine --models vgg16 \
    --requests 16 --verify sampled --verify-rate 1.0 --inject bit_flip
# plan-equivalence smoke: a mixed enclave/blinded tier-1 PlacementPlan
# (inexpressible as any legacy mode string) through the async engine,
# cross-checked bit-exactly against the synchronous path on the same plan
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.serve --smoke --engine --models vgg16 \
    --requests 8 --plan mixed
# sharded-offload smoke: a mixed plan served over 2 simulated devices
# with device 1 dishonest — the drill fails unless every corruption is
# caught by the SHARD-local Freivalds check, only the bad shard is
# re-dispatched, quarantine is per-DEVICE (device 0 keeps serving
# blinded offload; the model is never quarantined), and responses stay
# bit-exact vs the single-device legacy server (DESIGN.md §11)
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.serve --smoke --engine --models vgg16 \
    --requests 8 --plan mixed --devices 2 --shard rows --inject bit_flip
# observability smoke: the same sharded drill with span tracing on — the
# trace artifact (queue -> batch -> plan steps -> shard dispatches ->
# verify -> unseal, DESIGN.md §13) must come out as valid Chrome-trace
# JSON with a connected tree, and the metrics snapshot must carry the §14
# phase decomposition (per-profile criticals summing to the request wall
# within 10%); CI uploads trace_tier1.json + metrics_tier1.json
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.serve --smoke --engine --models vgg16 \
    --requests 8 --plan mixed --devices 2 --shard rows --inject bit_flip \
    --verify full --trace-out trace_tier1.json \
    --metrics-out metrics_tier1.json --postmortem-dir postmortem_tier1
python - <<'PY'
import json
doc = json.load(open("trace_tier1.json"))
ev = [e for e in doc["traceEvents"] if e["ph"] == "X"]
roots = [e for e in ev if e["name"] == "request"]
assert roots and len(ev) > len(roots), (len(ev), len(roots))
names = {e["name"] for e in ev}
need = {"request", "queue", "batch", "unseal", "plan.segment",
        "shard.dispatch", "verify", "seal"}
assert need <= names, need - names
print(f"[trace] OK: {len(ev)} spans, {len(roots)} requests, "
      f"kinds={sorted({e['cat'] for e in ev})}")
m = json.load(open("metrics_tier1.json"))
ph = m["phases"]
assert ph["requests"] == len(roots), (ph["requests"], len(roots))
for key, prof in ph["profiles"].items():
    err = abs(prof["critical_sum_s"] - prof["wall_s"])
    assert err <= 0.10 * prof["wall_s"] + 1e-9, (key, prof)
# the dishonest device triggered verify-failure post-mortem bundles, and
# every bundle is redaction-safe JSON (spans carry shapes/timings only)
assert m["flight_recorder"]["dumps"] > 0, m["flight_recorder"]
import glob
bundles = glob.glob("postmortem_tier1/postmortem_*.json")
assert bundles, "no post-mortem bundle written"
for b in bundles:
    json.load(open(b))
print(f"[phases] OK: {ph['requests']} requests decomposed, "
      f"{len(bundles)} post-mortem bundle(s)")
PY
# compile-once smoke (DESIGN.md §15): AOT-warm every shape bucket at
# register time with a persistent on-disk compilation cache — the request
# path must pay ZERO compile seconds (that is the compile-once contract),
# responses stay bit-exact vs the legacy oracle, and the cache stats land
# in aot_tier1.json (uploaded alongside metrics_tier1.json)
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.serve --smoke --engine --models vgg16 \
    --requests 8 --aot-warm --compile-cache-dir .aot_cache_tier1 \
    --metrics-out aot_tier1.json
python - <<'PY'
import json
m = json.load(open("aot_tier1.json"))
aot = m["aot"]
assert aot["compiles"] > 0, aot
assert aot["request_compile_seconds"] == 0.0, aot
assert m["ttfb_warm_s"] < 1.0, m["ttfb_warm_s"]
print(f"[aot] OK: {aot['compiles']} compile(s) all off the request path "
      f"({aot['compile_seconds']:.1f}s warmup), stores={aot['stores']} "
      f"ttfb_warm={m['ttfb_warm_s'] * 1e3:.0f}ms buckets={m['buckets']}")
PY
# liveness chaos smoke: scripted crash on device 0 + hang on device 1
# (total blackout), a session-refill fault window and a sealing-
# corruption window — the drill fails unless every future resolves, the
# engine degrades to verified enclave-only serving and recovers
# automatically via breaker half-open probes, seal-window requests are
# rejected with mac_failed and nothing else, and every served response
# stays bit-exact vs the healthy oracle (DESIGN.md §12)
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} \
    python -m repro.launch.serve --smoke --engine --models vgg16 \
    --devices 2 --chaos "dev0.crash@1-2,dev1.hang@1-2,refill@7-8,seal@10"
# private-decode smoke (DESIGN.md §16): blinded ring-fed autoregressive
# generation on the smollm smoke config with full per-step Freivalds —
# tokens AND logits must be bit-exact vs the trusted=True enclave oracle,
# every offloaded op verified, one ring slot consumed per decode step;
# CI uploads decode_tier1.json
PYTHONPATH=src${PYTHONPATH:+:$PYTHONPATH} python - <<'PY'
import json
import jax
import numpy as np
from repro.configs import get_smoke
from repro.core import integrity as IG
from repro.models import model as M
from repro.runtime import generate as G

cfg = get_smoke("smollm_135m")
params = M.init_params(cfg, jax.random.PRNGKey(0))
prompt = jax.random.randint(jax.random.PRNGKey(1), (2, 6), 0,
                            cfg.vocab_size)
kw = dict(max_new_tokens=6, integrity=IG.IntegrityPolicy.full(k=2),
          session_key=jax.random.PRNGKey(9))
priv = G.private_generate(params, prompt, cfg, **kw)
oracle = G.private_generate(params, prompt, cfg, trusted=True, **kw)
assert np.array_equal(np.asarray(priv.tokens), np.asarray(oracle.tokens))
assert np.array_equal(np.asarray(priv.logits), np.asarray(oracle.logits))
assert priv.telemetry.device_matmuls > 0 and priv.telemetry.verify_ops > 0
assert priv.integrity.ok and priv.integrity.n_checked == priv.integrity.n_ops
assert priv.ring["consumed"] == priv.decode_steps, priv.ring
json.dump({"plan_digest": priv.plan_digest,
           "decode_steps": priv.decode_steps,
           "verified_ops": int(priv.integrity.n_checked),
           "device_matmuls": int(priv.telemetry.device_matmuls),
           "ring": priv.ring,
           "tier1_cache_bytes": G.tier1_cache_bytes(cfg, 2, 12),
           "bitexact_vs_trusted": True},
          open("decode_tier1.json", "w"), indent=1)
print(f"[decode] OK: {priv.decode_steps} private decode steps bit-exact "
      f"vs trusted oracle, {int(priv.integrity.n_checked)} ops verified, "
      f"ring={priv.ring}")
PY
