"""Compile-once AOT serving (DESIGN.md §15): exactly-once compiles under
concurrency, shape-bucket dispatch, and persistent-cache fail-closed
behavior (disk hit on a clean entry, fresh compile on a corrupted one)."""
import threading

import jax
import numpy as np
import pytest

from repro.configs import get_smoke
from repro.models import model as M
from repro.runtime.aot import (CompileCache, bucket_for, bucket_ladder,
                               code_version, shape_signature)
from repro.runtime.engine import EngineConfig, ServingEngine
from repro.runtime.serving import PrivateInferenceServer, Request


@pytest.fixture(scope="module")
def vgg16():
    cfg = get_smoke("vgg16")
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0))


def _request(cfg, rid, rng):
    from repro.privacy.data import make_batch
    img = make_batch(rid, 1, cfg.image_size)[0]
    key = rng.integers(0, 2 ** 32 - 1, size=(2,), dtype=np.uint32)
    box = PrivateInferenceServer.client_seal(key, img, rid)
    return Request(rid=rid, box=box, shape=img.shape, session_key=key), key


# ---------------------------------------------------------------------------
# pure pieces: the bucket ladder and the cache key
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("env_dir", [None, "jaxcache-from-env"])
def test_persistent_compile_cache_dir(monkeypatch, tmp_path, env_dir):
    """JAX_COMPILATION_CACHE_DIR, when set, is used as it stands and the
    helper sets no other directory; unset, the cache lives at the fixed
    <checkout>/.jax_cache — never a temp-, pid- or time-derived path.
    Either way the key includes the op metadata (the device phases' name
    scopes), and locations carry no source frames."""
    from pathlib import Path
    from repro.runtime.aot import use_persistent_compile_cache
    before = jax.config.jax_compilation_cache_dir
    flags = ("jax_compilation_cache_include_metadata_in_key",
             "jax_traceback_in_locations_limit")
    kept = {f: getattr(jax.config, f) for f in flags}
    if env_dir is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                           str(tmp_path / env_dir))
    try:
        path = use_persistent_compile_cache()
        if env_dir is None:
            checkout = Path(__file__).resolve().parents[1]
            assert path == str(checkout / ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == path
            assert use_persistent_compile_cache() == path
        else:
            assert path == str(tmp_path / env_dir)
            assert jax.config.jax_compilation_cache_dir == before
        assert jax.config.jax_compilation_cache_include_metadata_in_key
        assert jax.config.jax_traceback_in_locations_limit == 0
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        for f, v in kept.items():
            jax.config.update(f, v)


def test_bucket_ladder_powers_of_two():
    assert bucket_ladder(4) == (1, 2, 4)
    assert bucket_ladder(8) == (1, 2, 4, 8)
    # non-power max_batch terminates the ladder exactly at max
    assert bucket_ladder(6) == (1, 2, 4, 6)
    assert bucket_ladder(1) == (1,)


def test_bucket_for_is_occupancy_driven():
    assert [bucket_for(n, 4) for n in (1, 2, 3, 4)] == [1, 2, 4, 4]
    assert bucket_for(5, 6) == 6  # clamped to max, not to 8
    with pytest.raises(AssertionError):
        bucket_for(0, 4)
    with pytest.raises(AssertionError):
        bucket_for(5, 4)


def test_entry_key_separates_kind_shape_and_plan():
    cache = CompileCache()
    a = np.zeros((4, 8), np.float32)
    b = np.zeros((2, 8), np.float32)
    k = cache.entry_key("digest0", "blinded", (a,))
    assert k != cache.entry_key("digest0", "trusted", (a,))
    assert k != cache.entry_key("digest0", "blinded", (b,))
    assert k != cache.entry_key("digest1", "blinded", (a,))
    assert k == cache.entry_key("digest0", "blinded", (a,))


def test_shape_signature_and_code_version_stable():
    tree = {"x": np.zeros((2, 3), np.int32)}
    assert shape_signature(tree) == "2x3:int32"
    assert code_version() == code_version()
    assert len(code_version()) == 16


def test_compile_once_exactly_once_under_races():
    cache = CompileCache()
    built = []

    def build():
        built.append(1)
        return "exe"

    results = []

    def worker():
        results.append(cache.compile_once("k", build))

    ts = [threading.Thread(target=worker) for _ in range(8)]
    [t.start() for t in ts]
    [t.join() for t in ts]
    assert len(built) == 1
    assert all(r[0] == "exe" for r in results)
    assert sum(fresh for _, fresh in results) == 1
    assert cache.counters["compiles"] == 1
    assert cache.counters["memo_hits"] == 7


# ---------------------------------------------------------------------------
# engine integration: exactly-once per (plan digest, shape bucket)
# ---------------------------------------------------------------------------

def test_concurrent_register_compiles_each_bucket_once(vgg16):
    """Two models sharing one plan digest, registered concurrently with
    AOT warm: the shared CompileCache compiles each (digest, kind, bucket)
    exactly once — the losing thread memo-hits every signature."""
    cfg, params = vgg16
    engine = ServingEngine(EngineConfig(max_batch=4, aot_warm=True))
    errs = []

    def register(name):
        try:
            engine.register_model(name, cfg, params)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    try:
        ts = [threading.Thread(target=register, args=(n,))
              for n in ("vgg16-a", "vgg16-b")]
        [t.start() for t in ts]
        [t.join() for t in ts]
        assert not errs
        c = engine.aot.counters
        # ladder (1,2,4) x (blinded, trusted) = 6 signatures; the second
        # registration resolves all 6 from the memo, never recompiling
        assert c["compiles"] == 6, c
        assert c["memo_hits"] == 6, c
        assert engine.aot.request_compile_seconds == 0.0
    finally:
        engine.close()


def test_mixed_shape_submits_compile_each_bucket_once(vgg16, rng):
    """Unwarmed engine: a full bucket-4 wave, a lone bucket-1 request and
    a repeat bucket-4 wave compile exactly two executables (one per
    bucket), with the repeat wave served entirely from the memo."""
    cfg, params = vgg16
    engine = ServingEngine(EngineConfig(max_batch=4, max_wait_ms=500.0))
    engine.register_model("vgg16", cfg, params)
    try:
        reqs = [_request(cfg, i, rng)[0] for i in range(9)]
        waves = [reqs[0:4], reqs[4:5], reqs[5:9]]
        for wave in waves:
            got = [f.result(timeout=300) for f in
                   [engine.submit("vgg16", r) for r in wave]]
            assert all(r.ok for r in got)
        c = engine.aot.counters
        # bucket 4 + bucket 1 — and NOT a third for the repeat wave: the
        # executor's own signature memo resolves it before the cache
        assert c["compiles"] == 2, c
        snap = engine.snapshot()
        assert set(snap["buckets"]) == {1, 4}
        assert snap["buckets"][4]["batches"] == 2
    finally:
        engine.close()


# ---------------------------------------------------------------------------
# persistent cache: disk hit on reboot, fail-closed on corruption
# ---------------------------------------------------------------------------

def _serve_one(cache_dir, cfg, params, rng, rid):
    engine = ServingEngine(EngineConfig(max_batch=4, max_wait_ms=50.0,
                                        compile_cache_dir=str(cache_dir)))
    engine.register_model("vgg16", cfg, params)
    try:
        req, key = _request(cfg, rid, rng)
        resp = engine.submit("vgg16", req).result(timeout=300)
        assert resp.ok, resp.error
        logits = PrivateInferenceServer.client_open(
            key, resp.box, (cfg.num_classes,))
        return logits, dict(engine.aot.counters)
    finally:
        engine.close()


def test_disk_cache_reboot_and_corruption(vgg16, rng, tmp_path):
    cfg, params = vgg16
    cache_dir = tmp_path / "aot"

    # cold boot: fresh compile, persisted
    logits0, c0 = _serve_one(cache_dir, cfg, params, rng, 7000)
    assert c0["compiles"] >= 1
    if c0["stores"] == 0:
        pytest.skip("jax build lacks serialize_executable: memo-only cache")

    # warm boot (new engine = empty memo): loaded from disk, zero compiles,
    # bit-exact logits
    logits1, c1 = _serve_one(cache_dir, cfg, params, rng, 7000)
    assert c1["compiles"] == 0, c1
    assert c1["disk_hits"] >= 1, c1
    np.testing.assert_array_equal(logits0, logits1)

    # corrupt every persisted entry: the loader must fail closed to a
    # fresh compile (counted), never to a failed request
    entries = list(cache_dir.glob("*.xc"))
    assert entries
    for p in entries:
        p.write_bytes(b"not a pickle")
    logits2, c2 = _serve_one(cache_dir, cfg, params, rng, 7000)
    assert c2["disk_errors"] >= 1, c2
    assert c2["compiles"] >= 1, c2
    np.testing.assert_array_equal(logits0, logits2)
