"""Span-tracing overhead: identical engine workloads, tracing off vs on.

The observability acceptance bar (DESIGN.md §13): full span tracing —
request/queue/batch/session/step/shard/verify spans plus the metrics
registry — must cost <5% wall time on the tier-1 smoke shapes. Kernel
spans are OFF here, as in production: their block_until_ready fences are
a profiling mode, priced separately by the ``span_us`` micro row.

Methodology: two warmed engines over the same smoke vgg16 weights and a
mixed (enclave/blinded) PlacementPlan, one with no tracer and one with a
live Tracer. OFF/ON rounds interleave so machine drift lands on both
sides equally, and medians are compared — a single GC pause or noisy
neighbour can't fake (or mask) a regression.
"""
from __future__ import annotations

import statistics
import time
from typing import Callable, Dict

import jax

ROUNDS = 4
REQS_PER_ROUND = 4
THRESHOLD_PCT = 5.0

BENCH_CONFIG = {
    "model": "vgg16 (smoke)",
    "plan": "mixed",
    "rounds": ROUNDS,
    "requests_per_round": REQS_PER_ROUND,
    "threshold_pct": THRESHOLD_PCT,
}


def _build_engine(tracer):
    from repro.configs import get_smoke
    from repro.core import plan as PL
    from repro.models import model as M
    from repro.runtime.engine import EngineConfig, ServingEngine

    cfg = get_smoke("vgg16")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    engine = ServingEngine(EngineConfig(max_batch=REQS_PER_ROUND,
                                        max_wait_ms=10.0), tracer=tracer)
    engine.register_model("vgg16", cfg, params,
                          placement=PL.make_mixed(cfg))
    return engine, cfg


def _round(engine, cfg, rid0: int) -> float:
    from repro.launch.serve import _sealed_requests
    reqs, _ = _sealed_requests(cfg, REQS_PER_ROUND, rid0=rid0)
    t0 = time.perf_counter()
    futures = [engine.submit("vgg16", r) for r in reqs]
    resps = [f.result(timeout=300) for f in futures]
    dt = time.perf_counter() - t0
    assert all(r.ok for r in resps), [r.error for r in resps if not r.ok]
    return dt


def run_suite(emit: Callable[[str, float, str], None]) -> Dict[str, Dict]:
    from repro.core.tracing import Tracer

    tracer = Tracer()
    eng_off, cfg = _build_engine(None)
    eng_on, _ = _build_engine(tracer)
    try:
        # warm compiles + caches out of the timings (one round each)
        _round(eng_off, cfg, rid0=90_000)
        _round(eng_on, cfg, rid0=91_000)

        off_s, on_s = [], []
        for i in range(ROUNDS):
            off_s.append(_round(eng_off, cfg, rid0=1_000 * i))
            on_s.append(_round(eng_on, cfg, rid0=50_000 + 1_000 * i))
    finally:
        eng_off.close()
        eng_on.close()

    med_off = statistics.median(off_s)
    med_on = statistics.median(on_s)
    overhead_pct = (med_on - med_off) / med_off * 100.0
    n_spans = len(tracer.spans())

    # micro row: raw span create+end cost, amortized (the per-event price
    # every instrumented site pays, independent of engine wall noise)
    t = Tracer()
    n_micro = 20_000
    t0 = time.perf_counter()
    for _ in range(n_micro):
        with t.span("micro", "step", k=1):
            pass
    span_us = (time.perf_counter() - t0) / n_micro * 1e6

    ok = overhead_pct < THRESHOLD_PCT
    results = {
        "engine_mixed_plan": {
            "off_s": [round(x, 4) for x in off_s],
            "on_s": [round(x, 4) for x in on_s],
            "median_off_s": round(med_off, 4),
            "median_on_s": round(med_on, 4),
            "overhead_pct": round(overhead_pct, 2),
            "threshold_pct": THRESHOLD_PCT,
            "pass": ok,
            "spans_recorded": n_spans,
        },
        "span_micro": {"span_us": round(span_us, 3), "iters": n_micro},
    }
    emit("trace/engine_overhead", med_on * 1e6,
         f"off={med_off:.3f}s on={med_on:.3f}s "
         f"overhead={overhead_pct:+.2f}% ({'OK' if ok else 'FAIL'}) "
         f"spans={n_spans}")
    emit("trace/span_create_end", span_us, f"iters={n_micro}")
    if not ok:
        print(f"trace_overhead: FAIL — {overhead_pct:+.2f}% >= "
              f"{THRESHOLD_PCT}% threshold")
    return results
