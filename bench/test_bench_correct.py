"""The comparison that decides ``correct``, driven on the CPU at a small
size: a whole run (set-up, window, sampled comparison) with the chip check
skipped comes out correct; with an answer altered where the program
produces it, or with the control's answers in the served ones' place, it
comes out not correct. A traced run reads its counters and spans before
the profiler starts, and the trace over the window's last seconds."""
from __future__ import annotations

import json
import sys
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(ROOT / "src"))

import run as R  # noqa: E402

SMALL = {
    "vgg16": dict(layers=["conv8", "conv8", "pool", "conv16", "conv16",
                          "pool", "fc32", "logits"],
                  image_size=32, num_classes=10),
}
# the control's test size: VGG's depth of open layers (their error adds up
# layer by layer) at a width and an image size the CPU runs in seconds
CONTROL_SIZE = {
    "vgg16": dict(layers=["conv64", "conv64", "pool", "conv128", "conv128",
                          "pool", "conv256", "conv256", "conv256", "pool",
                          "conv512", "conv512", "pool", "fc512", "fc512",
                          "logits"],
                  image_size=32, num_classes=100),
}
# (traffic, the end-to-end metric besides setup_s) of each configuration
TRAFFIC = {"vgg16": ("backlog", "images_per_s")}


def small_cell(cfg, size=SMALL):
    """A cell of ``cfg`` at a size the CPU runs in seconds, built from the
    configuration's and the traffic's files."""
    traffic, metric = TRAFFIC[cfg]
    conf = json.loads((BENCH / "configs" / f"{cfg}.json").read_text())
    mix = json.loads((BENCH / "traffic" / f"{traffic}.json").read_text())
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    name = f"{cfg}.private.{traffic}"
    return R.Cell(
        name=name, chips=1, conf=dict(conf, **size[cfg]),
        traffic=dict(mix, warmup_s=min(mix.get("warmup_s", 0), 1.0)),
        workload={"engine": {"max_batch": 2, "max_wait_ms": 5.0,
                             "pipeline_depth": 2}},
        module=BENCH / "configs" / f"{cfg}.py",
        end_to_end=[{"name": "setup_s", "unit": "s"},
                    {"name": metric, "unit": "x"}],
        per_layer=[m for m in spec["per_layer"]
                   if name in m.get("workloads", [name])])


@pytest.fixture
def quiet_jax(monkeypatch):
    """A run sets process-wide JAX options and a compile cache; keep them
    inside the test."""
    from repro.runtime import aot
    monkeypatch.setattr(aot, "use_persistent_compile_cache", lambda: "")
    keep = {k: getattr(jax.config, k) for k in (
        "jax_default_matmul_precision",
        "jax_persistent_cache_min_compile_time_secs")}
    yield
    for k, v in keep.items():
        jax.config.update(k, v)


def _quiet_log(*a, **k):
    pass


def _alter_answers(monkeypatch):
    """Break the timed path underneath: the program's answer (a logit) is
    altered where it is produced."""
    from repro.core.origami import OrigamiExecutor
    infer = OrigamiExecutor.infer

    def altered(self, batch, *a, **kw):
        res = infer(self, batch, *a, **kw)
        lg = res.logits
        res.logits = lg.at[0, 0].add(0.05 * jnp.max(jnp.abs(lg[0])))
        return res

    monkeypatch.setattr(OrigamiExecutor, "infer", altered)


@pytest.mark.parametrize("cfg", ["vgg16"])
@pytest.mark.parametrize("mode", ["served", "altered", "control"])
def test_a_run_decides_correct(cfg, mode, quiet_jax, monkeypatch):
    """The control needs VGG's depth of open layers to read above the
    limit (its error adds up layer by layer), so it runs at that size."""
    cell = small_cell(cfg, CONTROL_SIZE if mode == "control" else SMALL)
    if mode == "altered":
        _alter_answers(monkeypatch)
    out = R.run(cell, seed=2 ** 33 + 7, seconds=1.0, trace=False,
                control=mode == "control", log=_quiet_log)
    assert out["correct"] is (mode == "served"), out["compared"]
    if mode != "served":
        assert any(v["value"] > v["limit"]
                   for v in out["compared"].values()), out["compared"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert list(out["compared"])[-1] == "sampled_batches"
    assert set(out["metrics"]) == {m["name"] for m in cell.end_to_end}


@pytest.mark.parametrize("cfg", ["vgg16"])
def test_control_fails_the_limit(cfg, quiet_jax):
    """The control (the reference with its open products in int8) reads above
    the configuration's limit on the sizes a test can hold, where the
    reference checked against itself reads 0."""
    cell = small_cell(cfg, CONTROL_SIZE)
    mod = R.load_module(cell.module, f"ctl_{cfg}")
    model = mod.Model(cell.conf, cell.traffic)
    rng, key = R.seed_streams(11)
    payloads = np.stack([p for _, p in model.make_pool(4, key, rng)])
    ref = jax.jit(lambda x: mod.reference_logits(cell.conf, model.params, x))
    outs = np.asarray(ref(jnp.asarray(payloads)))
    batches = [(4, list(zip(payloads, outs)))]
    (limit_key, limit), = cell.conf["limits"].items()
    assert model.reference_readings(batches)[limit_key] == 0.0
    assert model.reference_readings(batches, control=True)[limit_key] > limit


def test_a_traced_run_reads_the_trace_last(quiet_jax, monkeypatch):
    """The profiler runs for the window's last TRACE_S seconds; counter and
    span metrics read the part before it, the trace's metrics the trace."""
    import trace_reduce
    seconds = R.TRACE_S + 1.5
    edges = []
    monkeypatch.setattr(jax.profiler, "start_trace",
                        lambda d: edges.append(("start", time.perf_counter())))
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: edges.append(("stop", time.perf_counter())))
    monkeypatch.setattr(trace_reduce, "find_xplane", lambda d: d)
    monkeypatch.setattr(trace_reduce, "reduce_file",
                        lambda p: trace_reduce.Reduction(
                            window_s=2.0, busy_s=1.5, n_devices=1,
                            op_seconds={"%_field_matmul_jit.1": 0.1}))
    runs, make = [], R.Run
    monkeypatch.setattr(R, "Run",
                        lambda **kw: runs.append(make(**kw)) or runs[-1])
    cell = small_cell("vgg16")
    out = R.run(cell, seed=5, seconds=seconds, trace=True, log=_quiet_log)
    assert out["correct"], out["compared"]
    (start, t_start), (stop, t_stop) = edges
    run, = runs
    assert (start, stop) == ("start", "stop")
    assert t_start - run.load.t0 == pytest.approx(seconds - R.TRACE_S,
                                                  abs=0.3)
    assert t_stop >= run.load.t1
    assert run.untraced_s == pytest.approx(seconds - R.TRACE_S)
    assert run.untraced_delta["engine.batches"] > 0
    assert (run.untraced_delta["engine.batches"]
            + run.trace_delta["engine.batches"]
            == run.delta["engine.batches"])
    got = out["metrics"]
    assert got["device_idle_share.images"]["value"] == pytest.approx(25.0)
    assert 0 < got["unseal_ms.images"]["value"]
    assert got["batch_fill.images"]["value"] > 0
    # no peaks off a TPU: no share of a peak or a roofline is made up
    assert "mfu.images" not in got
    assert "limb_matmul_roofline.images" not in got
    assert set(out["device"]) >= {"busy_s", "window_s"}
    assert out["breakdown"]["device_ops"] == [["%_field_matmul_jit.1", 0.1]]
