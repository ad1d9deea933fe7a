"""Performance-attribution plane (DESIGN.md §14): critical-path phase
folding (exact wall decomposition, compile isolation, parallel-child
interval merging), the flight recorder's redaction-enforced post-mortem
bundles (including a live quarantine-triggered bundle byte-scanned for
the run's secrets), and the bench_check regression gate."""
import json
import pathlib
import re
import sys

import numpy as np
import pytest

from repro.core.tracing import RedactionError, Span, Tracer
from repro.runtime.profiling import (PHASES, CriticalPathProfiler,
                                     FlightRecorder, _merge_intervals,
                                     phase_of)

SENTINEL = 0.91827364  # seeds the quarantine drill's plaintext input


class FakeTracer:
    """Hand-built span store — ``ingest`` only needs ``spans()``."""

    def __init__(self, spans):
        self._spans = list(spans)
        self.dropped = 0

    def spans(self):
        return list(self._spans)


def _span(sid, parent, name, t0, t1, kind="step", **attrs):
    return Span(trace_id=1, span_id=sid, parent_id=parent, name=name,
                kind=kind, t0=t0, t1=t1, attrs=attrs)


def _tree(rid=1, model="m", plan="abc", t0=0.0, infer_dur=1.0,
          first_call=False, base_sid=0, flops=1000):
    """request(4s wall) -> queue(1s) + batch -> unseal(0.5) + infer + seal.

    Laid out with known gaps so every phase's expected critical seconds
    are hand-computable.
    """
    sid = base_sid
    spans = [
        _span(sid + 1, None, "request", t0, t0 + 4.0, model=model,
              plan=plan, shape=[8, 8, 3], rid=rid),
        _span(sid + 2, sid + 1, "queue", t0, t0 + 1.0),
        _span(sid + 3, sid + 1, "batch", t0 + 1.0, t0 + 3.5, plan=plan),
        _span(sid + 4, sid + 3, "unseal", t0 + 1.0, t0 + 1.5),
        _span(sid + 5, sid + 3, "infer", t0 + 1.5,
              t0 + 1.5 + infer_dur, first_call=first_call,
              device_flops=flops, blind_bytes=64, unblind_bytes=32),
        _span(sid + 6, sid + 3, "seal", t0 + 3.2, t0 + 3.5),
    ]
    return spans


def test_phase_taxonomy_is_total():
    for name in ("queue", "unseal", "seal", "session.acquire",
                 "factors.sync", "factors.prefetch", "op.blinded",
                 "op.trusted", "shard.matmul", "shard.dispatch",
                 "shard.enclave", "infer", "plan.segment", "verify",
                 "batch", "request"):
        assert phase_of(name) in PHASES
    assert phase_of("some.future.span") == "other"   # never drops time


def test_merge_intervals():
    assert _merge_intervals([]) == []
    assert _merge_intervals([(0, 1), (2, 3)]) == [(0, 1), (2, 3)]
    assert _merge_intervals([(0, 2), (1, 3), (2.5, 4)]) == [(0, 4)]
    assert _merge_intervals([(1, 2), (0, 5)]) == [(0, 5)]


def test_fold_attributes_every_instant_exactly_once():
    prof = CriticalPathProfiler()
    assert prof.ingest(FakeTracer(_tree())) == 1
    (key, p), = prof.profiles.items()
    assert key == ("m", "abc", "8x8x3")
    crit = p.critical_s
    # hand-computed: queue 1.0; unseal 0.5; infer 1.0 (device_compute);
    # seal 0.3; batch self = 2.5 - (0.5 + 1.0 + 0.3) = 0.7 (other);
    # request self = 4.0 - (1.0 + 2.5) = 0.5 (other)
    assert crit["queue_wait"] == pytest.approx(1.0)
    assert crit["unseal"] == pytest.approx(0.5)
    assert crit["device_compute"] == pytest.approx(1.0)
    assert crit["seal"] == pytest.approx(0.3)
    assert crit["other"] == pytest.approx(1.2)
    # THE invariant: per-phase criticals sum to the request wall exactly
    assert sum(crit.values()) == pytest.approx(p.wall_s) == pytest.approx(4.0)
    # ingest is incremental: same store again folds nothing new
    assert prof.ingest(FakeTracer(_tree())) == 0


def test_parallel_children_do_not_double_claim():
    """Two overlapping shard dispatches under one shard.matmul: critical
    charges the covered extent once; total charges both durations."""
    spans = [
        _span(1, None, "request", 0.0, 3.0, model="m", plan="d",
              shape=[4]),
        _span(2, 1, "shard.matmul", 0.0, 3.0),
        _span(3, 2, "shard.dispatch", 0.5, 2.0),
        _span(4, 2, "shard.dispatch", 1.0, 2.5),   # overlaps [1.0, 2.0]
    ]
    prof = CriticalPathProfiler()
    prof.ingest(FakeTracer(spans))
    p = prof.profiles[("m", "d", "4")]
    # dispatches cover [0.5, 2.5] -> matmul self (dispatch_wait) = 1.0
    assert p.critical_s["dispatch_wait"] == pytest.approx(1.0)
    assert p.critical_s["device_compute"] == pytest.approx(2.0)
    assert p.total_s["device_compute"] == pytest.approx(1.5 + 1.5)
    assert sum(p.critical_s.values()) == pytest.approx(3.0)


def test_unfinished_and_non_request_roots_are_skipped():
    prof = CriticalPathProfiler()
    open_root = _span(1, None, "request", 0.0, None, model="m")
    stray = _span(2, None, "batch", 0.0, 1.0)
    assert prof.ingest(FakeTracer([open_root, stray])) == 0
    assert prof.ingest(None) == 0                 # engines without a tracer


def test_compile_isolation_first_call_minus_warm_median():
    prof = CriticalPathProfiler()
    spans = []
    # first call: infer takes 1.7s; three warm calls: 0.5s each
    spans += _tree(rid=1, infer_dur=1.7, first_call=True, base_sid=0)
    for i in range(3):
        spans += _tree(rid=2 + i, t0=10.0 * (i + 1), infer_dur=0.5,
                       base_sid=100 * (i + 1))
    prof.ingest(FakeTracer(spans))
    p = prof.profiles[("m", "abc", "8x8x3")]
    assert p.compile_s == pytest.approx(1.2)      # 1.7 - median(0.5)
    summ = p.summary()
    assert summ["compile_s"] == pytest.approx(1.2)
    # carved OUT of device_compute, and the sum-to-wall invariant holds
    assert summ["critical_s"]["compile"] == pytest.approx(1.2)
    assert summ["critical_s"]["device_compute"] == pytest.approx(
        1.7 + 3 * 0.5 - 1.2)
    assert summ["critical_sum_s"] == pytest.approx(summ["wall_s"])
    # report rolls the same numbers up
    rep = prof.report()
    assert rep["requests"] == 4
    assert rep["critical_s"]["compile"] == pytest.approx(1.2)


def test_cost_observations_warm_trees_only():
    prof = CriticalPathProfiler()
    spans = list(_tree(rid=1, infer_dur=2.0, first_call=True, flops=500))
    spans += _tree(rid=2, t0=10.0, infer_dur=0.5, base_sid=100, flops=500)
    prof.ingest(FakeTracer(spans))
    obs = prof.cost_observations()
    assert len(obs) == 1                          # first-call tree excluded
    quantities, seconds = obs[0]
    assert quantities["device_flops"] == 500
    assert quantities["blind_bytes"] == 64
    assert seconds["device_compute"] == pytest.approx(0.5)


def test_export_gauges():
    from repro.runtime.observability import MetricsRegistry
    prof = CriticalPathProfiler()
    prof.ingest(FakeTracer(_tree()))
    reg = MetricsRegistry()
    prof.export_gauges(reg)
    g = reg.snapshot()["gauges"]
    assert g["phase.requests"] == 1
    assert g["phase.queue_wait_s"] == pytest.approx(1.0)


# -- flight recorder -------------------------------------------------------

def test_flight_recorder_events_and_dump(tmp_path):
    rec = FlightRecorder(capacity=4, out_dir=str(tmp_path),
                         min_interval_s=0.0)
    for i in range(6):
        rec.event("shard_crash", device="dev0", i=i)
    assert len(rec.events) == 4                   # bounded ring
    tr = Tracer()
    s = tr.start_span("request", "request", model="m")
    tr.end(s)
    from repro.runtime.observability import MetricsRegistry
    reg = MetricsRegistry()
    reg.inc("integrity.quarantines")
    b1 = rec.dump("quarantine", tracer=tr, registry=reg, model="m")
    assert b1["trigger"] == "quarantine"
    assert [e["attrs"]["i"] for e in b1["events"]] == [2, 3, 4, 5]
    assert b1["spans"][0]["name"] == "request"
    assert b1["metrics"]["counter_delta"] == {"integrity.quarantines": 1}
    # second dump reports only the delta since the first
    reg.inc("integrity.quarantines", 2)
    b2 = rec.dump("quarantine", tracer=tr, registry=reg)
    assert b2["metrics"]["counter_delta"] == {"integrity.quarantines": 2}
    files = sorted(tmp_path.glob("postmortem_*.json"))
    assert [f.name for f in files] == ["postmortem_000_quarantine.json",
                                       "postmortem_001_quarantine.json"]
    assert json.loads(files[0].read_text())["trigger"] == "quarantine"
    assert rec.snapshot()["dumps"] == 2


def test_flight_recorder_rate_limits_per_trigger():
    rec = FlightRecorder(min_interval_s=3600.0)
    assert rec.dump("verify_failure") is not None
    assert rec.dump("verify_failure") is None     # same kind: suppressed
    assert rec.dump("degradation") is not None    # other kind: allowed
    assert rec.suppressed == 1


def test_flight_recorder_redaction_fails_closed():
    rec = FlightRecorder()
    with pytest.raises(RedactionError):
        rec.event("oops", payload=np.arange(8))
    assert len(rec.events) == 0
    with pytest.raises(RedactionError):
        rec.dump("manual", secret=b"key")


def test_flight_recorder_caps_disk_dumps(tmp_path):
    rec = FlightRecorder(out_dir=str(tmp_path), min_interval_s=0.0,
                         max_dumps=2)
    for _ in range(4):
        rec.dump("manual")
    assert len(list(tmp_path.glob("*.json"))) == 2
    assert rec.snapshot()["dumps"] == 4           # ring keeps counting


# -- the acceptance drill: injected quarantine -> redacted bundle ----------

@pytest.fixture(scope="module")
def quarantine_bundle(tmp_path_factory):
    """A dishonest device flips bits under full verification with
    ``quarantine_after=1`` — the first flagged batch must quarantine the
    model AND dump a post-mortem bundle; the bundle is byte-scanned for
    the run's actual secrets (sentinel-seeded input, session keys,
    logits)."""
    import jax
    from repro.configs import get_smoke
    from repro.core.integrity import IntegrityPolicy
    from repro.models import model as M
    from repro.runtime.engine import EngineConfig, ServingEngine
    from repro.runtime.faults import DishonestDevice, FaultSpec
    from repro.runtime.serving import PrivateInferenceServer, Request

    out_dir = tmp_path_factory.mktemp("postmortem")
    cfg = get_smoke("vgg16")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tracer = Tracer()
    rec = FlightRecorder(out_dir=str(out_dir), min_interval_s=0.0)
    engine = ServingEngine(
        EngineConfig(max_batch=2, max_wait_ms=20.0, quarantine_after=1),
        tracer=tracer, recorder=rec)
    entry = engine.register_model(
        "vgg16", cfg, params, mode="origami",
        integrity=IntegrityPolicy.full(1),
        fault=DishonestDevice(FaultSpec("bit_flip")))
    img = np.full((cfg.image_size, cfg.image_size, 3), SENTINEL,
                  np.float32)
    key = np.array([0xFEEDC0DE, 0x87654321], dtype=np.uint32)
    box = PrivateInferenceServer.client_seal(key, img, 3)
    resp = engine.submit("vgg16", Request(
        rid=3, box=box, shape=img.shape, session_key=key)).result(
        timeout=300)
    assert resp.ok, resp.error
    logits = PrivateInferenceServer.client_open(key, resp.box,
                                                (cfg.num_classes,))
    snap = engine.snapshot()
    engine.close()
    return {"snap": snap, "entry": entry, "out_dir": out_dir,
            "recorder": rec, "img": img, "key": key, "logits": logits}


def test_quarantine_dumps_postmortem_bundle(quarantine_bundle):
    snap = quarantine_bundle["snap"]
    assert snap["models"]["vgg16"]["quarantined"]
    assert snap["integrity"]["quarantines"] == 1
    names = [f.name for f in
             sorted(quarantine_bundle["out_dir"].glob("*.json"))]
    assert any("quarantine" in n for n in names), names
    assert any("verify_failure" in n for n in names), names
    bundle = quarantine_bundle["recorder"].last_bundle
    assert bundle["trigger"] in ("quarantine", "verify_failure")
    assert bundle["metrics"]["counter_delta"]
    assert any(s["name"] == "request" for s in bundle["spans"])
    # the engine also exports the recorder state in its snapshot
    assert snap["flight_recorder"]["dumps"] == len(names)


def test_postmortem_bundle_carries_no_secret_material(quarantine_bundle):
    """PR 7 byte-scan contract extended to post-mortem bundles: the files
    CI uploads must structurally exclude client inputs, key material and
    logits (redaction already rejects arrays; this catches any future
    text-smuggle path too)."""
    blobs = [(f.name, f.read_text()) for f in
             sorted(quarantine_bundle["out_dir"].glob("*.json"))]
    assert blobs
    key = quarantine_bundle["key"]
    forbidden_text = [f"{SENTINEL:.8f}"[:9]]
    forbidden_text += [str(int(w)) for w in key if int(w) > 10 ** 6]
    for v in np.asarray(quarantine_bundle["logits"]).ravel():
        if abs(v) > 1e-3:
            forbidden_text.append(np.format_float_positional(
                v, precision=6, trim="-"))
    for name, text in blobs:
        raw = text.encode()
        assert key.tobytes() not in raw
        assert quarantine_bundle["img"].tobytes()[:4096] not in raw
        for ft in forbidden_text:
            pat = re.compile(rf"(?<![\d.]){re.escape(ft)}(?![\d.])")
            assert not pat.search(text), \
                f"secret {ft!r} leaked into {name}"


def test_engine_snapshot_phases_decompose_wall(quarantine_bundle):
    """The tentpole surface: snapshot()["phases"] decomposes the traced
    round with compile isolated and criticals summing to wall."""
    phases = quarantine_bundle["snap"]["phases"]
    assert phases["requests"] == 1
    assert set(phases["taxonomy"]) == set(PHASES)
    (key, prof), = phases["profiles"].items()
    model, digest, bucket = key.split("|")
    assert model == "vgg16"
    assert digest == quarantine_bundle["entry"].executor.plan.digest[:12]
    assert prof["critical_sum_s"] == pytest.approx(prof["wall_s"],
                                                   rel=1e-6)
    assert prof["critical_s"]["unseal"] > 0
    assert prof["critical_s"]["seal"] > 0


# -- bench_check regression gate -------------------------------------------

def _bench_check():
    root = pathlib.Path(__file__).resolve().parent.parent
    sys.path.insert(0, str(root / "scripts"))
    try:
        import bench_check
    finally:
        sys.path.pop(0)
    return bench_check


def test_bench_check_direction_bands():
    bc = _bench_check()
    # lower-is-better: regression only above base*(1+rel)+abs
    assert bc.check_metric(100.0, 150.0, "lower", 0.6, 0.0)
    assert not bc.check_metric(100.0, 161.0, "lower", 0.6, 0.0)
    assert bc.check_metric(1.0, 5.0, "lower", 0.0, 4.0)
    # higher-is-better: regression only below base*(1-rel)-abs
    assert bc.check_metric(10.0, 6.0, "higher", 0.5, 0.0)
    assert not bc.check_metric(10.0, 4.0, "higher", 0.5, 0.0)
    assert bc.check_metric(1.0, 1.0, "higher", 0.0, 0.0)  # exact pin holds
    assert not bc.check_metric(1.0, 0.99, "higher", 0.0, 0.0)


def test_bench_check_passes_committed_baselines():
    """The committed baselines must gate green against the committed
    fresh artifacts (they are seeded from them)."""
    bc = _bench_check()
    root = pathlib.Path(__file__).resolve().parent.parent
    base_dir = root / "benchmarks" / "baselines"
    assert base_dir.is_dir(), "benchmarks/baselines/ missing"
    fails = []
    for suite, fname in bc.FILES.items():
        base, fresh = base_dir / fname, root / fname
        if not base.exists() or not fresh.exists():
            continue
        fails += bc.check_suite(suite, json.loads(base.read_text()),
                                json.loads(fresh.read_text()))
    assert fails == []


def test_bench_check_fails_synthetic_regression(tmp_path):
    bc = _bench_check()
    base = {"results": {"load_burst": {"achieved_rps": 6.0},
                        "engine": {"time_to_first_batch_s": 8.0}}}
    # 10x throughput collapse: outside the 0.6 rel band
    regressed = {"results": {"load_burst": {"achieved_rps": 0.6},
                             "engine": {"time_to_first_batch_s": 8.0}}}
    fails = bc.check_suite("serving", base, regressed)
    assert len(fails) == 1 and "achieved_rps" in fails[0]
    # a vanished metric fails loudly too
    gone = {"results": {"engine": {"time_to_first_batch_s": 8.0}}}
    fails = bc.check_suite("serving", base, gone)
    assert any("missing" in f for f in fails)
    # and the same docs inside the band pass
    assert bc.check_suite("serving", base, base) == []
