#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip this process is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --sweep 2,4,8

Everything a cell needs is found by name: the cell in ``BENCHMARK.json``,
its configuration in ``bench/configs/<config>.json`` with the module
beside it (weights, sealed inputs, plain reference), its traffic in
``bench/traffic/<mix>.json`` (read by ``loadgen.py``), its engine settings
in ``bench/workloads/<cell>.json``, and each metric's reader in
``bench/metrics/<metric>.py``.

The run makes its inputs from ``--seed``, warms every shape it will use
and runs the traffic's warm-up load (set-up), measures for ``--seconds``, then compares a sample of the
window's answers, drawn from the seed, with the plain reference. Its last
stdout line is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer ones), ``device`` and, traced, ``breakdown``. It exits non-zero,
printing no result, off a TPU or with fewer chips than the cell asks for.
``--sweep`` offers each listed rate (requests/s) in turn, in one process,
to an open-loop cell, and prints one line per rate.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Any, Dict, List, Optional

BENCH = Path(__file__).resolve().parent
ROOT = BENCH.parent
sys.path.insert(0, str(BENCH))

SAMPLE_BATCHES = 8       # batches of the window compared with the reference
TRACE_S = 2.0            # traced part of a --trace 1 window: longer traces
                         # overflow the device's event buffer (the MAC loop
                         # of a 224x224 unseal is 150531 steps)
POOL_READY_S = 120.0     # longest wait for the session pool to fill


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    conf: Dict[str, Any]
    traffic: Dict[str, Any]
    workload: Dict[str, Any]
    module: Path
    end_to_end: List[Dict[str, Any]]
    per_layer: List[Dict[str, Any]]


def _read_json(path: Path) -> Any:
    if not path.is_file():
        raise SystemExit(f"bench: missing {path.relative_to(ROOT)}")
    return json.loads(path.read_text())


def _applies(metric: Dict[str, Any], cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(name: str, spec: Optional[Dict[str, Any]] = None) -> Cell:
    """Resolve a cell and every file it needs by name (no JAX)."""
    spec = spec if spec is not None else _read_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"bench: no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    module = BENCH / "configs" / f"{w['config']}.py"
    if not module.is_file():
        raise SystemExit(f"bench: missing {module.relative_to(ROOT)}")
    cell = Cell(name=name, chips=int(w["chips"]),
                conf=_read_json(BENCH / "configs" / f"{w['config']}.json"),
                traffic=_read_json(BENCH / "traffic" / f"{w['traffic']}.json"),
                workload=_read_json(BENCH / "workloads" / f"{name}.json"),
                module=module,
                end_to_end=[m for m in spec["end_to_end"]
                            if _applies(m, name)],
                per_layer=[m for m in spec["per_layer"] if _applies(m, name)])
    for m in cell.end_to_end + cell.per_layer:
        reader_path(m["name"])
    return cell


def reader_path(metric: str) -> Path:
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.is_file():
        raise SystemExit(f"bench: missing {path.relative_to(ROOT)}")
    return path


def load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def seed_streams(seed: int):
    """A numpy generator and a JAX key, both from ``seed`` (any size)."""
    import jax
    import numpy as np
    ss = np.random.SeedSequence(seed)
    w0, w1 = (int(v) for v in ss.generate_state(2, np.uint32))
    return (np.random.default_rng(ss.spawn(1)[0]),
            jax.random.fold_in(jax.random.PRNGKey(w0), w1))


def counters(engine, model: str) -> Dict[str, float]:
    """One cut of every counter a metric reads."""
    out = dict(engine.registry.snapshot()["counters"])
    for k, v in engine.models[model].pool.stats().items():
        out[f"pool.{k}"] = v
    out["aot.compiles"] = engine.aot.stats()["compiles"]
    return out


@dataclasses.dataclass
class Run:
    """What a metric reader sees of one run."""
    cell: Cell
    seconds: float
    setup_s: float
    load: Any                         # loadgen.LoadResult
    delta: Dict[str, float]           # counters over the window
    buckets: List[int]
    model: Any
    peaks: Dict[str, float]
    spans: List[Any]                  # the program's spans (core/tracing)
    span_epoch: float                 # perf_counter time of their t0 = 0
    trace: Any = None                 # trace_reduce.Reduction
    trace_delta: Optional[Dict[str, float]] = None   # over the trace
    untraced_delta: Optional[Dict[str, float]] = None  # before the trace
    untraced_s: float = 0.0           # window seconds before the trace


def _peaks(kind: str) -> Dict[str, float]:
    table = json.loads((BENCH / "peaks.json").read_text())
    if kind not in table["devices"]:
        raise SystemExit(f"bench: no peaks for device kind {kind!r} in "
                         "bench/peaks.json")
    return table["devices"][kind]


def _warm(engine, gen, buckets: List[int]) -> None:
    """One batch of every bucket through the whole served path: submit,
    batch, infer, seal, open."""
    import loadgen
    for b in buckets:
        sent = []
        for _ in range(b):
            i = gen._take()
            sent.append((i, engine.submit(gen.model, gen.pool[i][0])))
        engine.flush()
        for i, fut in sent:
            resp = fut.result(timeout=loadgen.DRAIN_S * 10)
            if not resp.ok:
                raise RuntimeError(f"warm-up request failed: {resp.error}")
            gen.open_fn(gen.pool[i], resp)
            gen._busy.discard(i)


def _wait_pool(engine, model: str) -> None:
    """Wait until the session pool holds its full depth and its refill
    thread has gone quiet, so the window starts from a filled pool."""
    pool = engine.models[model].pool
    if pool._cache() is None:
        return
    t_end = time.perf_counter() + POOL_READY_S
    last, since = None, time.perf_counter()
    while time.perf_counter() < t_end:
        st = pool.stats()
        now = time.perf_counter()
        if st["refilled"] != last:
            last, since = st["refilled"], now
        elif st["pending"] >= st["depth"] and now - since >= 0.5:
            return
        time.sleep(0.05)


def _batches(tracer, model: str, load, max_batch: int):
    """Batches of the window as the engine formed them: (bucket, [Sent])."""
    from repro.runtime.aot import bucket_for
    by_rid: Dict[int, List[Any]] = {}
    for s in load.sent:
        by_rid.setdefault(s.rid, []).append(s)
    out = []
    for sp in tracer.spans():
        if sp.name != "batch" or sp.attrs.get("model") != model:
            continue
        t = tracer.epoch + sp.t0
        if not (load.t0 <= t < load.t1):
            continue
        rows = []
        for rid in sp.attrs.get("rids", []):
            for s in by_rid.get(rid, []):
                if s.submitted <= t and (s.done is None or t <= s.done):
                    rows.append(s)
        if rows and all(s.ok for s in rows):
            out.append((bucket_for(len(rows), max_batch), rows))
    return out


def _log_timeline(load, tracer, model: str, devs, delta, log) -> None:
    """Where the window's time went, on the host: answers opened in each
    tenth of the window, the longest stretches with no batch starting,
    and the device allocator's state at the close."""
    n = 10
    step = (load.t1 - load.t0) / n
    per = [0] * n
    for s in load.done_in_window():
        per[min(n - 1, int((s.done - load.t0) / step))] += 1
    starts = sorted(tracer.epoch + sp.t0 for sp in tracer.spans()
                    if sp.name == "batch" and sp.attrs.get("model") == model
                    and load.t0 <= tracer.epoch + sp.t0 < load.t1)
    gaps = sorted(((b - a, a - load.t0) for a, b in zip(starts, starts[1:])),
                  reverse=True)[:3]
    mem = devs[0].memory_stats() or {}
    log(f"[window] opened per tenth {per}; longest gaps between batch "
        f"starts (s, at s) {[(round(g, 3), round(t, 1)) for g, t in gaps]}; "
        f"pool {({k: v for k, v in delta.items() if k.startswith('pool.')})}"
        f"; device memory { {k: mem.get(k) for k in ('bytes_in_use', 'peak_bytes_in_use', 'largest_free_block_bytes', 'num_allocs')} }",
        file=sys.stderr)


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        rates: Optional[List[float]] = None, control: bool = False,
        log=print) -> Dict[str, Any]:
    """The whole run after the chip check. Returns the result object.
    ``control`` puts the lower-precision control's answers in the served
    answers' place in the comparison, so that the run reads the control
    against the limits (the benchmark's own runs leave it off)."""
    import jax
    import numpy as np
    import loadgen
    import readers
    import trace_reduce
    from repro.core.tracing import Tracer
    from repro.runtime.aot import bucket_ladder, use_persistent_compile_cache
    from repro.runtime.engine import EngineConfig, ServingEngine

    t_setup = time.perf_counter()
    use_persistent_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    devs = jax.devices()[:cell.chips]
    peaks = _peaks(devs[0].device_kind) if devs[0].platform == "tpu" else {}
    rng, key = seed_streams(seed)
    mod = load_module(cell.module, f"bench_config_{cell.conf['name']}")
    model = mod.Model(cell.conf, cell.traffic)
    tracer = Tracer(kernel_spans=False)
    eng_cfg = EngineConfig(aot_warm=True, **cell.workload["engine"])
    engine = ServingEngine(eng_cfg, tracer=tracer)
    try:
        name = model.register(engine)
        buckets = list(bucket_ladder(eng_cfg.max_batch))
        pool = model.make_pool(int(cell.traffic["pool"]), key, rng)
        gen = loadgen.LoadGenerator(engine, name, pool, model.open)
        t_reg = time.perf_counter()
        _warm(engine, gen, buckets)
        t_warm = time.perf_counter()
        _wait_pool(engine, name)
        t_pool = time.perf_counter()
        log(f"[setup] register+pool {t_reg - t_setup:.3f} s, warm pass "
            f"{t_warm - t_reg:.3f} s, pool fill {t_pool - t_warm:.3f} s, "
            f"then {cell.traffic.get('warmup_s', 0)} s of load",
            file=sys.stderr)
        if rates:
            return _sweep(cell, engine, gen, name, rates, seconds, rng, log)

        cuts: Dict[str, Dict[str, float]] = {}
        trace_dir = tempfile.mkdtemp(prefix="bench-trace-")
        # the profiler records the window's last TRACE_S seconds, from a
        # steady state; counter and span metrics read the part before it
        untraced_s = max(0.0, seconds - TRACE_S) if trace else seconds

        def on_window(edge: str) -> None:
            cuts[edge] = counters(engine, name)
            if edge == "trace_open":
                jax.profiler.start_trace(trace_dir)
            elif edge == "close" and trace:
                jax.profiler.stop_trace()

        marks = [(untraced_s, "trace_open")] if trace else []
        load = gen.run(cell.traffic, seconds, rng, on_window, marks)
        setup_s = load.t0 - t_setup
        log(f"[setup] {setup_s:.3f} s, executables compiled "
            f"{engine.aot.stats()['compiles']}, compile seconds "
            f"{engine.aot.stats().get('compile_seconds', 0):.3f}",
            file=sys.stderr)
        peak = max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
                   for d in devs)
        delta = {k: cuts["close"].get(k, 0) - cuts["open"].get(k, 0)
                 for k in cuts["close"]}
        trace_delta = ({k: v - cuts["trace_open"].get(k, 0)
                        for k, v in cuts["close"].items()}
                       if trace else None)
        untraced_delta = ({k: v - cuts["open"].get(k, 0)
                           for k, v in cuts["trace_open"].items()}
                          if trace else delta)
        if delta.get("aot.compiles", 0):
            log(f"[window] {delta['aot.compiles']} executables compiled "
                "inside the window", file=sys.stderr)
        late = load.lateness_s
        log(f"[loadgen] sent {len(load.sent)}, lateness p50 "
            f"{readers.quantile(late, 0.5)} s p99 "
            f"{readers.quantile(late, 0.99)} s max "
            f"{max(late, default=None)} s", file=sys.stderr)
        _log_timeline(load, tracer, name, devs, delta, log)
        batches = _batches(tracer, name, load, eng_cfg.max_batch)
    finally:
        engine.close()
    del engine, gen
    gc.collect()

    red = None
    if trace:
        red = trace_reduce.reduce_file(trace_reduce.find_xplane(trace_dir))
        shutil.rmtree(trace_dir, ignore_errors=True)
        for name_, secs in red.top_ops(40):
            log(f"[trace] {secs:.6f} s {name_}", file=sys.stderr)
        log(f"[trace] busy {red.busy_s:.6f} s of {red.window_s:.6f} s, "
            f"gaps {red.idle_gaps}", file=sys.stderr)
    r = Run(cell=cell, seconds=seconds, setup_s=setup_s, load=load,
            delta=delta, buckets=buckets, model=model, peaks=peaks,
            spans=tracer.spans(), span_epoch=tracer.epoch,
            trace=red, trace_delta=trace_delta,
            untraced_delta=untraced_delta, untraced_s=untraced_s)

    # the comparison that decides correct
    idx = rng.permutation(len(batches))[:SAMPLE_BATCHES]
    sample = [batches[i] for i in sorted(idx)]
    longest = max(range(len(batches)), key=lambda i: len(batches[i][1]),
                  default=None)
    if longest is not None and longest not in idx:
        sample.append(batches[longest])
    compare = [(b, [(pool[s.idx][1], s.output) for s in rows])
               for b, rows in sample]
    readings = (model.reference_readings(compare, control=control)
                if compare else {})
    if control:
        log("[control] the control's answers stand in the served ones",
            file=sys.stderr)
    limits = cell.conf["limits"]
    attempted = len(load.due_in_window() if cell.traffic["loop"] == "open"
                    else load.open_in_window())
    failed = sum(1 for s in load.sent if not s.ok)
    checks = {k: (readings.get(k), v) for k, v in limits.items()}
    correct = bool(compare) and failed == 0 and all(
        got is not None and got <= lim for got, lim in checks.values())

    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        reader = load_module(reader_path(m["name"]),
                             f"bench_metric_{m['name'].replace('.', '_')}")
        value = reader.read(r)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs), "memory_peak_bytes": peak}
    out = {"correct": correct, "attempted": attempted, "failed": failed,
           "metrics": metrics, "device": device}
    if red is not None:
        device["busy_s"] = red.busy_s
        device["window_s"] = red.window_s
        out["breakdown"] = {"device_ops": red.top_ops(10),
                            "idle_gaps": [list(g) for g in red.idle_gaps]}
    compared = {k: {"value": got, "limit": lim}
                for k, (got, lim) in checks.items()}
    compared["failed_requests"] = {"value": failed, "limit": 0}
    compared["sampled_batches"] = {"value": len(compare), "limit": 1}
    out["compared"] = compared
    for k, v in compared.items():
        log(f"[compare] {k} {v['value']} limit {v['limit']}",
            file=sys.stderr)
    return out


def _sweep(cell, engine, gen, name, rates, seconds, rng, log):
    """Offer each rate in turn to the open loop; one line per rate."""
    import readers
    rows = []
    for rate in rates:
        traffic = dict(cell.traffic, loop="open", rate_per_s=rate)
        t_before = counters(engine, name)
        load = gen.run(traffic, seconds, rng)
        done = load.done_in_window()
        due = load.due_in_window()
        lat = [(s.done - s.due) if s.ok and s.done else float("inf")
               for s in due]
        depth_end = sum(1 for s in due if s.done is None or s.done > load.t1)
        row = {"offered_rps": rate, "completed_rps": len(done) / seconds,
               "sent": len(due), "late_at_close": depth_end,
               "p50_ms": readers.quantile(lat, 0.5) * 1e3,
               "p95_ms": readers.quantile(lat, 0.95) * 1e3,
               "batches": counters(engine, name)["engine.batches"]
               - t_before["engine.batches"]}
        log(json.dumps(row), flush=True)
        rows.append(row)
    return {"sweep": rows}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates (requests/s), open loop")
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="compare the lower-precision control in the "
                    "served answers' place (it has to come out not correct)")
    args = ap.parse_args(argv)
    cell = load_cell(args.workload)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the program (src/repro) is not in this checkout",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    import jax
    devices = jax.devices()
    if devices[0].platform != "tpu" or len(devices) < cell.chips:
        print(f"bench: {cell.name} needs {cell.chips} TPU chip(s); JAX "
              f"found {len(devices)} {devices[0].platform} device(s)",
              file=sys.stderr)
        return 3
    rates = ([float(x) for x in args.sweep.split(",")] if args.sweep
             else None)
    out = run(cell, args.seed, args.seconds, bool(args.trace), rates,
              bool(args.control))
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
