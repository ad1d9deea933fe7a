"""Observability plane: metrics registry atomicity, span-tree tracing over
a full blinded+verified+sharded request, mandatory redaction (fail-closed
attach + byte-scan of the serialized trace), registry/legacy agreement,
and the shared BENCH_*.json metadata envelope."""
import json
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.core import plan as PL
from repro.core import tracing
from repro.core.integrity import IntegrityPolicy
from repro.core.origami import OrigamiExecutor
from repro.core.tracing import RedactionError, Tracer, redact
from repro.models import model as M
from repro.runtime.devices import DevicePool
from repro.runtime.engine import EngineConfig, EngineStats, ServingEngine
from repro.runtime.faults import DishonestDevice, FaultSpec
from repro.runtime.observability import MetricsRegistry
from repro.runtime.serving import PrivateInferenceServer, Request

SENTINEL = 0.98765432  # seeds the plaintext input the byte-scan hunts for


# -- metrics registry ------------------------------------------------------

def test_registry_counters_gauges_histograms():
    reg = MetricsRegistry()
    assert reg.inc("engine.submitted") == 1
    assert reg.inc("engine.submitted", 4) == 5
    reg.inc_many(**{"shard.checks": 3, "shard.failures": 1, "noop": 0})
    assert reg.get("shard.checks") == 3
    assert reg.get("noop") == 0          # zero deltas are not materialized
    reg.gauge("engine.queue_depth", 7)
    for v in (0.1, 0.2, 0.3, 0.9):
        reg.observe("engine.latency_s", v)
    snap = reg.snapshot()
    assert snap["counters"]["engine.submitted"] == 5
    assert snap["gauges"]["engine.queue_depth"] == 7
    h = snap["histograms"]["engine.latency_s"]
    assert h["count"] == 4 and h["max"] == 0.9 and h["p50"] == 0.2
    assert reg.quantile("engine.latency_s", 0.95) == 0.9
    reg.reset("shard.")
    assert reg.get("shard.checks") == 0
    assert reg.get("engine.submitted") == 5


def test_engine_stats_concurrent_hammer():
    """Satellite 1: the old bare `+=` counters lost increments under
    concurrency; the registry-backed facade must not. Hammer from many
    threads through every legacy mutation spelling and diff exact totals."""
    stats = EngineStats()
    n_threads, iters = 8, 400

    def worker():
        for _ in range(iters):
            stats.inc("submitted")
            stats.inc_many(batches=1, batched_requests=2, padded_slots=1)
            with stats.lock:                # legacy compound block
                stats.inc("completed")
                stats.inc("verify_checks", 3)
            stats.record_done(0.01)

    threads = [threading.Thread(target=worker) for _ in range(n_threads)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    total = n_threads * iters
    assert stats.submitted == total
    assert stats.batches == total
    assert stats.batched_requests == 2 * total
    assert stats.padded_slots == total
    assert stats.completed == 2 * total      # inc + record_done
    assert stats.verify_checks == 3 * total
    assert len(stats.latencies) == min(total, EngineStats.LAT_WINDOW)


def test_engine_stats_lock_is_registry_lock():
    stats = EngineStats()
    assert stats.lock is stats.registry.lock


# -- redaction: fail closed at attach time ---------------------------------

def test_redact_allowlist_passes_scalars_and_containers():
    assert redact(None) is None
    assert redact(True) is True
    assert redact(7) == 7
    assert redact(0.5) == 0.5
    assert redact("digest:ab12") == "digest:ab12"
    assert redact([1, 2, (3, "x")]) == [1, 2, [3, "x"]]
    assert redact({"shape": [224, 224, 3]}) == {"shape": [224, 224, 3]}
    long = "x" * 10_000
    assert len(redact(long)) == 513          # truncated, ellipsis appended


def test_redact_rejects_secret_bearing_types():
    for bad in (np.zeros(4, np.int32), jnp.zeros((2, 2)),
                b"\x00keymaterial", bytearray(b"kk"),
                memoryview(b"kk"), object()):
        with pytest.raises(RedactionError):
            redact(bad)
    # nested inside an allowed container: still rejected
    with pytest.raises(RedactionError):
        redact({"ok": 1, "oops": np.arange(3)})
    with pytest.raises(RedactionError):
        redact([[[[1]]]])                    # too deep
    with pytest.raises(RedactionError):
        redact(list(range(100)))             # too long


def test_span_attach_fails_closed():
    """A disallowed attach raises AND stores nothing — the span never
    enters the store with the secret, and annotate-after keeps the span
    clean of the rejected attribute."""
    tr = Tracer()
    with pytest.raises(RedactionError):
        tr.start_span("bad", "step", r=np.arange(8, dtype=np.int32))
    assert tr.spans() == []                  # rejected before the append
    s = tr.start_span("ok", "step", n=1)
    with pytest.raises(RedactionError):
        tr.annotate(s, leak=jnp.ones(3))
    assert s.attrs == {"n": 1}
    tr.end(s)


# -- device phases and the session factors --------------------------------

def _hlo(jitted, *args, **kw) -> str:
    return jitted.lower(*args, **kw).compile().as_text()


def _op_names(hlo: str):
    import re
    return set(re.findall(r'op_name="([^"]*)"', hlo))


def _phases_in(hlo: str):
    from repro.core.tracing import DEVICE_PHASES
    return {part for name in _op_names(hlo) for part in name.split("/")
            if part in DEVICE_PHASES}


def test_device_phase_scopes_in_lowered_hlo():
    """Every device phase is a name scope the compiled HLO carries in its
    op metadata: the sealing cores each under their own (the client's open
    apart from the enclave's unseal, one body), a blinded executor's trace
    under the tier-1 and tier-2 phases and, deriving its factors live,
    the factor phases — which the precompute cache's per-layer program
    carries too."""
    from repro.core import sealing as S
    from repro.core.precompute import _layer_factors
    from repro.core.tracing import DEVICE_PHASES
    from repro.kernels.limb_matmul.ref import P
    key = jnp.zeros((2,), jnp.uint32)
    ct = jnp.zeros((3, 4), jnp.uint32)
    nonce = jnp.zeros((2,), jnp.uint32)
    assert _phases_in(_hlo(S._unseal_core, key, ct, nonce)) == {
        "enclave.unseal"}
    assert _phases_in(_hlo(S._open_core, key, ct, nonce)) == {"client.open"}
    assert _phases_in(_hlo(S._seal_core, key, jnp.zeros((5,)), nonce)) == {
        "enclave.seal"}
    cfg = get_smoke("vgg16")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    found = set()
    for impl in ("fused", "unfused"):
        ex = OrigamiExecutor(cfg, params, impl=impl,
                             integrity=IntegrityPolicy.full(1))
        x = jnp.zeros((2, cfg.image_size, cfg.image_size, 3), jnp.float32)
        found |= _phases_in(_hlo(ex._aot_jit, params, {"images": x},
                                 jax.random.PRNGKey(1), None))
    assert found == set(DEVICE_PHASES) - {"enclave.unseal", "client.open",
                                          "enclave.seal"}
    w = jnp.asarray(np.random.default_rng(0).integers(0, P, (16, 8),
                                                      dtype=np.int32))
    assert _phases_in(_hlo(
        _layer_factors, jax.random.PRNGKey(2), 0, w, layer=0, t=4, d_in=16,
        d_out=8, pad=True, k=1, shards=2)) == {
        "factors.pad", "factors.unblind", "factors.fold"}


def test_sealing_programs_agree_bit_for_bit():
    """The client's open and the enclave's unseal are one body: the same
    words and the same tag, and a tampered box fails both."""
    from repro.core import sealing as S
    key = jnp.asarray([7, 9], jnp.uint32)
    x = jnp.asarray(np.random.default_rng(3).normal(size=(6, 5)), jnp.float32)
    box = S.seal(key, x, jnp.asarray([1, 2, 3], jnp.uint32))
    pt_e, ok_e = S.unseal(key, box, (6, 5))
    pt_c, ok_c = S.open_response(key, box, (6, 5))
    assert ok_e and ok_c
    np.testing.assert_array_equal(np.asarray(pt_e), np.asarray(x))
    np.testing.assert_array_equal(np.asarray(pt_c), np.asarray(x))
    bad = box._replace(mac=box.mac ^ jnp.uint32(1))
    assert not S.unseal(key, bad, (6, 5))[1]
    assert not S.open_response(key, bad, (6, 5))[1]


def _pooled_executor():
    from repro.core.origami import OrigamiExecutor
    cfg = get_smoke("vgg16")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    ex = OrigamiExecutor(cfg, params, precompute=True,
                         integrity=IntegrityPolicy.full(1))
    batch = {"images": jnp.zeros((2, cfg.image_size, cfg.image_size, 3),
                                 jnp.float32)}
    ex.build_cache(batch)
    return ex, batch


def test_take_counts_sets_made_on_the_request_path():
    """``take`` counts every set a request takes, and apart those it had
    to make itself (a ``factors.sync`` span under the request's tracer):
    one for a key the pool never prefetched, none for one it did. The
    pool's ``stats`` sums them over the executor's caches."""
    from repro.runtime.sessions import SessionPool
    ex, _ = _pooled_executor()
    tr = Tracer()
    pool = SessionPool(ex, depth=2, background=False, tracer=tr)
    cold = pool.acquire()                 # nothing prefetched yet
    with tr.span("infer", "infer"), tracing.activate(tr, tr.spans()[-1]):
        ex.cache.take(cold)
    assert (ex.cache.factors_taken, ex.cache.factors_sync) == (1, 1)
    sync, = [s for s in tr.spans() if s.name == "factors.sync"]
    assert sync.kind == "session" and sync.parent_id is not None
    assert sync.attrs == {"layers": len(ex.cache.layers), "bucket": 2}
    pool.prime()
    warm = pool.acquire()
    assert ex.cache.prefetched(warm)
    ex.cache.take(warm)
    assert (ex.cache.factors_taken, ex.cache.factors_sync) == (2, 1)
    st = pool.stats()
    assert (st["factors_taken"], st["factors_sync"]) == (2, 1)
    assert st["misses"] == 1              # the acquire-time count stays


def test_refill_failures_are_traced_not_raised():
    """A prefetch that raises is counted (``refill_errors``) and recorded
    as a ``factors.prefetch`` root span carrying the exception's class
    name; a good one as the same span without it."""
    from repro.runtime.sessions import SessionPool
    ex, _ = _pooled_executor()
    tr = Tracer()

    def fault(counter):
        if counter == 0:
            raise KeyError("refill fault")

    pool = SessionPool(ex, depth=2, background=False, tracer=tr,
                       refill_fault=fault)
    pool.prime()
    spans = [s for s in tr.spans() if s.name == "factors.prefetch"]
    assert [s.attrs.get("counter") for s in spans] == [0, 1]
    assert spans[0].attrs["error"] == "KeyError"
    assert "error" not in spans[1].attrs
    assert all(s.parent_id is None and s.t1 is not None and
               s.attrs["n_caches"] == 1 for s in spans)
    assert pool.stats()["refill_errors"] == 1
    assert pool.stats()["refilled"] == 1


def test_refill_thread_traces_each_prefetch():
    """Under an engine with a tracer, the session pool's refill thread
    records one ``factors.prefetch`` root span per session it prefetches,
    and the batch that finds its factors prefetched records no
    ``factors.sync``."""
    import time
    cfg = get_smoke("vgg16")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tracer = Tracer()
    engine = ServingEngine(EngineConfig(max_batch=1, max_wait_ms=1.0,
                                        session_pool_depth=2),
                           tracer=tracer)
    try:
        entry = engine.register_model("vgg16", cfg, params, mode="origami")
        img = np.zeros((cfg.image_size, cfg.image_size, 3), np.float32)
        key = np.array([1, 2], dtype=np.uint32)
        for rid in (1, 2):
            box = PrivateInferenceServer.client_seal(key, img, rid)
            resp = engine.submit("vgg16", Request(
                rid=rid, box=box, shape=img.shape,
                session_key=key)).result(timeout=300)
            assert resp.ok, resp.error
            t_end = time.monotonic() + 60
            while (entry.pool.stats()["pending"] < 2
                   and time.monotonic() < t_end):
                time.sleep(0.02)
            time.sleep(0.2)
    finally:
        engine.close()
    spans = tracer.spans()
    prefetch = [s for s in spans if s.name == "factors.prefetch"]
    assert len(prefetch) >= 2
    assert all(s.parent_id is None and s.kind == "session"
               and {"counter", "n_caches"} <= set(s.attrs)
               for s in prefetch)
    assert {s.tid for s in prefetch} != {threading.get_ident()}
    st = entry.pool.stats()
    assert st["factors_taken"] == 2
    assert st["factors_sync"] == 1       # the first batch, before the pool
    assert len([s for s in spans if s.name == "factors.sync"]) == 1


# -- the acceptance run: one traced request, mixed + verified + sharded ----

@pytest.fixture(scope="module")
def traced_run():
    """One engine request through a mixed blinded+enclave+verified-open
    plan (``bbevvooo`` — inexpressible as any legacy mode) with full
    Freivalds verification, row-sharded over 2 simulated devices with
    device 1 flipping bits — so the trace must cover queue -> batch ->
    session -> plan steps (all three regimes) -> shard dispatches
    (including the verify-failed attempt and its retry) -> verify ->
    unseal."""
    cfg = get_smoke("vgg16")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    tracer = Tracer()
    engine = ServingEngine(EngineConfig(max_batch=2, max_wait_ms=20.0),
                           tracer=tracer)
    entry = engine.register_model(
        "vgg16", cfg, params,
        placement=PL.from_string(cfg, "bbevvooo",
                                 verify=IntegrityPolicy.full(1)),
        integrity=IntegrityPolicy.full(1),
        devices=DevicePool(2, faults={1: DishonestDevice(
            FaultSpec("bit_flip"))}),
        shard="rows")
    img = np.full((cfg.image_size, cfg.image_size, 3), SENTINEL,
                  np.float32)
    key = np.array([0xDEADBEEF, 0x12345678], dtype=np.uint32)
    box = PrivateInferenceServer.client_seal(key, img, 7)
    resp = engine.submit("vgg16", Request(
        rid=7, box=box, shape=img.shape, session_key=key)).result(
        timeout=300)
    assert resp.ok, resp.error
    logits = PrivateInferenceServer.client_open(key, resp.box,
                                                (cfg.num_classes,))
    snap = engine.snapshot()
    issued = [np.frombuffer(kb, np.uint32).copy()
              for kb in entry.pool._issued]
    factors = entry.executor.cache.session_factors(
        jnp.asarray(issued[0])) if issued else []
    tele = entry.executor.telemetry_blinded
    tele_cut = {"blinded_bytes": tele.blinded_bytes,
                "offloaded_flops": tele.offloaded_flops}
    engine.close()
    return {"tracer": tracer, "snap": snap, "client_key": key,
            "img": img, "logits": logits, "issued": issued,
            "factors": factors, "resp": resp, "tele": tele_cut}


def test_span_tree_connected_and_complete(traced_run):
    tr = traced_run["tracer"]
    # the session pool's refill thread records its prefetches as roots of
    # their own (no request context on that thread)
    prefetch = [s for s in tr.spans() if s.name == "factors.prefetch"]
    assert all(s.parent_id is None and s.kind == "session"
               for s in prefetch)
    spans = [s for s in tr.spans() if s.name != "factors.prefetch"]
    roots = [s for s in tr.roots() if s.name != "factors.prefetch"]
    assert len(roots) == 1 and roots[0].name == "request"
    root = roots[0]
    # every span connects to the single request root (one trace, one tree)
    by_id = tr.by_id()
    for s in spans:
        cur = s
        hops = 0
        while cur.parent_id is not None:
            cur = by_id[cur.parent_id]
            hops += 1
            assert hops < 50
        assert cur.span_id == root.span_id, f"{s.name} detached from root"
        assert s.trace_id == root.trace_id
    # no dangling spans: everything closed by the time the future resolved
    assert [s.name for s in spans if s.t1 is None] == []
    names = {s.name for s in spans}
    required = {"request", "queue", "batch", "unseal", "session.acquire",
                "infer", "plan.segment", "op.blinded", "shard.matmul",
                "shard.dispatch", "verify", "seal", "factors.sync"}
    assert required <= names, f"missing spans: {required - names}"
    # the dishonest device forces a failed attempt and a retry dispatch
    dispatches = [s for s in spans if s.name == "shard.dispatch"]
    outcomes = {s.attrs.get("outcome") for s in dispatches}
    attempts = {s.attrs.get("attempt") for s in dispatches}
    assert "verify_failed" in outcomes and "verified" in outcomes
    assert "retry" in attempts
    # both offload regimes are traced: blinded ops AND verified-open ops
    ops = [s for s in spans if s.name == "op.blinded"]
    assert any(s.attrs.get("verified_open") for s in ops)
    assert any(not s.attrs.get("verified_open") for s in ops)
    # parent/child sanity: timing nests inside the request root
    for s in spans:
        assert s.t0 >= root.t0 - 1e-6
        assert s.t1 <= (root.t1 or float("inf")) + 1e-6


def test_trace_exports_valid_chrome_json(traced_run, tmp_path):
    tr = traced_run["tracer"]
    out = tmp_path / "trace.json"
    n = tr.dump_chrome(out)
    doc = json.loads(out.read_text())
    ev = doc["traceEvents"]
    assert len(ev) == n
    xs = [e for e in ev if e["ph"] == "X"]
    assert len(xs) == len(tr.spans())
    for e in xs:
        assert e["dur"] >= 0 and e["ts"] >= 0
        assert {"trace_id", "span_id", "parent_id"} <= set(e["args"])
        assert e["cat"] in tracing.KINDS
    assert doc["otherData"]["dropped_spans"] == 0
    # JSONL export round-trips too
    outl = tmp_path / "trace.jsonl"
    assert tr.dump_jsonl(outl) == len(tr.spans())
    lines = [json.loads(ln) for ln in outl.read_text().splitlines()]
    assert {ln["name"] for ln in lines} == {s.name for s in tr.spans()}


def test_serialized_trace_carries_no_secret_material(traced_run, tmp_path):
    """Satellite 3: byte-scan the serialized trace for the run's actual
    secrets — blinding-factor field elements, session-key material (both
    the client sealing key and every pool-issued blinding key), and
    plaintext input / logit values (the input is sentinel-seeded so a leak
    cannot hide in noise)."""
    tr = traced_run["tracer"]
    chrome = json.dumps(tr.to_chrome())
    jsonl = "\n".join(json.dumps(s.as_dict()) for s in tr.spans())
    blob_text = chrome + "\n" + jsonl
    blob = blob_text.encode()

    # raw-byte forms (a binary smuggle would be a bug even in JSON)
    forbidden_bytes = [traced_run["client_key"].tobytes(),
                       traced_run["img"].tobytes()[:4096],
                       traced_run["logits"].tobytes()]
    for k in traced_run["issued"]:
        forbidden_bytes.append(k.tobytes())
    for e in traced_run["factors"]:
        r = e.get("r")
        if r is not None:
            forbidden_bytes.append(np.asarray(r).tobytes()[:4096])
    for fb in forbidden_bytes:
        assert fb not in blob

    # text forms (JSON serializes numbers as decimal text)
    forbidden_text = [f"{SENTINEL:.8f}"[:9]]          # plaintext input
    for k in traced_run["issued"] + [traced_run["client_key"]]:
        forbidden_text += [str(int(w)) for w in k if int(w) > 10 ** 6]
    for e in traced_run["factors"]:
        r = e.get("r")
        if r is not None:
            flat = np.asarray(r).ravel()[:64]
            forbidden_text += [str(int(v)) for v in flat
                               if int(v) > 10 ** 6][:16]
    for v in np.asarray(traced_run["logits"]).ravel():
        if abs(v) > 1e-3:
            forbidden_text.append(np.format_float_positional(
                v, precision=6, trim="-"))
    assert forbidden_text, "scan list unexpectedly empty"
    # delimiter-aware: a leaked value serializes as a standalone JSON
    # number/string token, while timestamp digit runs may contain any
    # short digit sequence as a substring — don't flake on those
    import re
    for ft in forbidden_text:
        pat = re.compile(rf"(?<![\d.]){re.escape(ft)}(?![\d.])")
        assert not pat.search(blob_text), \
            f"secret text {ft!r} leaked into trace"


def test_registry_agrees_with_legacy_surfaces(traced_run):
    """The consolidated registry must read back the same totals the legacy
    snapshot surfaces report — one accounting, two spellings."""
    snap = traced_run["snap"]
    metrics = snap["metrics"]
    c, g = metrics["counters"], metrics["gauges"]
    integ = snap["integrity"]
    assert c["integrity.verify_checks"] == integ["verify_checks"]
    assert c["shard.checks"] == integ["shard_checks"] > 0
    assert c["shard.failures"] == integ["shard_failures"] > 0
    assert c["shard.retries"] == integ["shard_retries"] > 0
    assert c["engine.submitted"] == snap["submitted"] == 1
    assert c["engine.completed"] == snap["completed"] == 1
    assert c["engine.batches"] == snap["batches"]
    # telemetry bridge: executor Telemetry == model.* gauges
    tele = traced_run["tele"]
    assert g["model.vgg16.telemetry.blinded_bytes"] == \
        tele["blinded_bytes"] > 0
    assert g["model.vgg16.telemetry.offloaded_flops"] == \
        tele["offloaded_flops"]
    # shard plane bridge: plane lifetime totals == model.*.shard gauges
    shard = snap["devices"]["vgg16"]["totals"]
    assert g["model.vgg16.shard.checks"] == shard["checks"]
    assert g["model.vgg16.shard.failures"] == shard["failures"]
    # latency histogram carries the one completed request
    assert metrics["histograms"]["engine.latency_s"]["count"] == 1


def test_device_and_watchdog_gauges_exported(traced_run):
    """Satellite 2: per-device breaker/quarantine state and the watchdog
    EWMAs are queryable as registry gauges (and still in the legacy
    snapshot)."""
    snap = traced_run["snap"]
    g = snap["metrics"]["gauges"]
    slots = snap["devices"]["vgg16"]["pool"]["slots"]
    for idx, slot in enumerate(slots):
        pre = f"device.vgg16.{idx}"
        assert g[f"{pre}.dispatches"] == slot["dispatches"]
        assert g[f"{pre}.quarantined"] == int(slot["quarantined"])
        assert g[f"{pre}.breaker_state"] in (0, 1, 2)
    # device 1 (the bit-flipper) was caught shard-locally; 0 stayed clean
    # (one request = one failed dispatch — below the quarantine threshold,
    # which the serve.py sharded drill exercises over a longer stream)
    assert g["device.vgg16.1.verify_failures"] >= 1
    assert g["device.vgg16.0.verify_failures"] == 0
    assert g["device.vgg16.0.quarantined"] == 0
    wd = snap["devices"]["vgg16"]["watchdog"]
    assert g["model.vgg16.shard.watchdog.p50_s"] == wd["p50_s"]
    assert g["model.vgg16.shard.watchdog.samples"] == wd["samples"]
    # the hard dispatch timeout has a cold fallback, so it is always a
    # number (the hedge deadline is None until the watchdog warms and is
    # then published too)
    assert g["model.vgg16.shard.watchdog.dispatch_timeout_s"] == \
        wd["dispatch_timeout_s"] > 0
    if wd["hedge_deadline_s"] is not None:
        assert g["model.vgg16.shard.watchdog.hedge_deadline_s"] == \
            wd["hedge_deadline_s"]
    assert "engine.watchdog.p50_s" in g


# -- bench metadata envelope ----------------------------------------------

def test_bench_meta_envelope(tmp_path):
    import pathlib
    import sys
    sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))
    try:
        from benchmarks import bench_meta
    finally:
        sys.path.pop(0)
    out = bench_meta.write_bench(tmp_path / "BENCH_x.json", "x",
                                 {"row": {"us": 1.0}}, config={"iters": 3})
    doc = json.loads(out.read_text())
    assert doc["meta"]["schema_version"] == bench_meta.SCHEMA_VERSION
    assert doc["meta"]["suite"] == "x"
    assert doc["meta"]["config"] == {"iters": 3}
    assert doc["meta"]["backend"] == jax.default_backend()
    assert doc["results"] == {"row": {"us": 1.0}}


# -- nearest-rank quantile helper (satellite: ONE rank-math impl) ----------

def test_nearest_rank_boundaries():
    from repro.runtime.observability import HIST_WINDOW, nearest_rank
    assert nearest_rank([], 0.5) == 0.0
    # n=1: the only sample answers every quantile
    for q in (0.0, 0.5, 0.95, 0.99, 1.0):
        assert nearest_rank([42.0], q) == 42.0
    # exact ranks on a full window: ceil(q*n)-th order statistic
    vals = list(range(1, HIST_WINDOW + 1))          # sorted 1..4096
    assert nearest_rank(vals, 0.0) == 1             # clamped to min
    assert nearest_rank(vals, 0.50) == 2048
    assert nearest_rank(vals, 0.95) == 3892         # ceil(0.95*4096)
    assert nearest_rank(vals, 0.99) == 4056         # ceil(0.99*4096)
    assert nearest_rank(vals, 1.0) == 4096


def test_hist_window_wraps_and_quantiles_follow():
    """Past HIST_WINDOW samples the ring drops the OLDEST: quantiles are
    computed over the surviving window, not the full stream."""
    from repro.runtime.observability import HIST_WINDOW
    reg = MetricsRegistry()
    for v in range(HIST_WINDOW + 100):              # 0..4195, keeps 100..4195
        reg.observe("lat", float(v))
    vals = reg.hist_values("lat")
    assert len(vals) == HIST_WINDOW
    assert min(vals) == 100.0 and max(vals) == float(HIST_WINDOW + 99)
    h = reg.snapshot()["histograms"]["lat"]
    assert h["count"] == HIST_WINDOW
    assert h["p50"] == 100.0 + 2048 - 1             # rank math on the window
    assert h["p99"] == 100.0 + 4056 - 1
    assert h["mean"] == pytest.approx(sum(vals) / HIST_WINDOW)
    assert reg.quantile("lat", 1.0) == h["max"]


# -- bounded-tracer truncation markers (satellite 2) -----------------------

def test_tracer_truncation_markers(tmp_path):
    tr = Tracer(max_spans=3)
    for i in range(5):
        tr.end(tr.start_span(f"s{i}", parent=None))
    assert len(tr.spans()) == 3 and tr.dropped == 2
    doc = tr.to_chrome()
    assert doc["otherData"]["truncated"] is True
    assert doc["otherData"]["dropped_spans"] == 2
    lines = []
    p = tmp_path / "t.jsonl"
    assert tr.dump_jsonl(p) == 3
    lines = [json.loads(l) for l in p.read_text().splitlines()]
    assert len(lines) == 4                          # 3 spans + marker
    assert lines[-1] == {"truncated": True, "dropped_spans": 2}
    # an unbounded-enough tracer emits NO marker anywhere
    tr2 = Tracer(max_spans=10)
    tr2.end(tr2.start_span("only", parent=None))
    assert tr2.to_chrome()["otherData"]["truncated"] is False
    tr2.dump_jsonl(p)
    lines = [json.loads(l) for l in p.read_text().splitlines()]
    assert len(lines) == 1 and "truncated" not in lines[0]
