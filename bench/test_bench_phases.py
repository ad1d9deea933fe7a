"""The phase reduction (``phases.py``): the HLO a trace carries, the
program's device phases in a trace recorded in the test, its spans laid on
the trace's clock by the tracer's anchors, and gaps named by program
spans; and the session-factor metric's reader. Stays off the TPU."""
from __future__ import annotations

import json
import sys
import time
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))
sys.path.insert(0, str(BENCH.parent / "src"))

import phases as P  # noqa: E402
import trace_reduce as T  # noqa: E402

FIXTURE = BENCH / "testdata" / "cpu_small.xplane.pb"
CPU = dict(device_plane=r"^/host:CPU$", op_line=r"^tf_XLAPjRtCpuClient")


def test_hlo_the_trace_carries():
    """The trace holds each executed module's HLO, with the op metadata
    the phases are read from, under the module's event name."""
    hlo = P.hlo_op_names(FIXTURE.read_bytes())
    assert hlo and all(name.endswith(")") for name in hlo)
    ops = {op: path for table in hlo.values() for op, path in table.items()}
    assert any(op.startswith("dot_general")
               and path.endswith("/dot_general") for op, path in ops.items())
    assert P.phase_of("jit(f)/tier1.unblind/tier1.matmul/jit(g)/tier1.blind"
                      "/mul", ("tier1.blind", "tier1.matmul",
                               "tier1.unblind")) == "tier1.blind"
    assert P.phase_of("jit(f)/tier1.matmulx/add", ("tier1.matmul",)) is None
    assert P._instruction("%fusion.4 = s32[8]{0} fusion(...)") == "fusion.4"


def _record(tmp_path, work):
    import jax
    jax.profiler.start_trace(str(tmp_path))
    try:
        out = work()
    finally:
        jax.profiler.stop_trace()
    return T.find_xplane(str(tmp_path)), out


def test_program_phases_in_a_recorded_trace(tmp_path):
    """Every device phase of the program shows in a trace of the served
    path's pieces: the sealing cores, a blinded executor's request and its
    session factors."""
    import jax
    import jax.numpy as jnp
    from repro.configs import get_smoke
    from repro.core import sealing as S
    from repro.core.integrity import IntegrityPolicy
    from repro.core.origami import OrigamiExecutor
    from repro.core.tracing import DEVICE_PHASES
    from repro.models import model as M
    cfg = get_smoke("vgg16")
    ex = OrigamiExecutor(cfg, M.init_params(cfg, jax.random.PRNGKey(0)),
                         precompute=True, integrity=IntegrityPolicy.full(1))
    batch = {"images": jnp.ones((2, cfg.image_size, cfg.image_size, 3))}
    key = jnp.asarray([3, 4], jnp.uint32)

    def work():
        box = S.seal(key, jnp.ones((10,)), jnp.asarray([1, 2], jnp.uint32))
        S.unseal(key, box, (10,))
        S.open_response(key, box, (10,))
        # the trace must hold the work, not stop while it is in flight
        return jax.block_until_ready(
            ex.infer(batch, session_key=jax.random.PRNGKey(1)).logits)

    work()                                 # compile outside the trace
    path, _ = _record(tmp_path, work)
    # on the CPU an op runs on whichever host thread is free, the calling
    # one included: take every line (events that are no op count as busy
    # and unattributed)
    red = P.reduce_file(path, DEVICE_PHASES, device_plane=r"^/host:CPU$",
                        op_line=r".")
    found = {k for k, v in red.phase_seconds.items() if v > 0}
    assert found - {P.UNATTRIBUTED} == set(DEVICE_PHASES)
    assert red.phase_seconds[P.UNATTRIBUTED] >= 0
    # phases may overlap on the CPU's threads, never leave busy time out
    assert sum(red.phase_seconds.values()) >= red.busy_s * (1 - 1e-9)
    # a program without the scopes gives no phase (and no error)
    none = P.reduce_file(path, (), device_plane=r"^/host:CPU$", op_line=r".")
    assert set(none.phase_seconds) == {P.UNATTRIBUTED}


def test_clock_anchor_lays_a_span_on_its_annotation(tmp_path):
    """A span opened inside a ``TraceAnnotation`` lands on that
    annotation's interval in the trace within 100 us, through the two
    anchors, which agree with each other as closely."""
    import jax
    import jax.numpy as jnp
    from jax.profiler import ProfileData, TraceAnnotation
    from repro.core.tracing import Tracer
    tr = Tracer()
    f = jax.jit(lambda x: x @ x)
    f(jnp.ones((64, 64))).block_until_ready()

    def work():
        tr.clock_anchor()
        with TraceAnnotation("work"):
            sp = tr.start_span("work", "step")
            f(jnp.ones((64, 64))).block_until_ready()
            time.sleep(0.01)
            tr.end(sp)
        time.sleep(0.05)
        tr.clock_anchor()
        return sp

    path, sp = _record(tmp_path, work)
    red = P.reduce_file(path, (), **CPU)
    off = red.clock_offsets_ns
    assert len(off) == 2
    assert red.clock_drift_ns <= 100e3
    ann, = [(int(e.start_ns), int(e.start_ns + e.duration_ns))
            for p in ProfileData.from_file(path).planes
            if p.name == "/host:CPU" for line in p.lines
            for e in line.events if e.name == "work"]
    mean = sum(off) / 2
    assert abs(sp.t0 * 1e9 + mean - ann[0]) <= 100e3
    assert abs(sp.t1 * 1e9 + mean - ann[1]) <= 100e3


def test_gaps_are_named_by_the_innermost_program_span():
    """A gap goes to the innermost span of the first tier that overlaps
    it, covering more than half; else to the span covering most; then to
    the next tier (the benchmark's annotations), then ``unattributed``."""
    batch = ("batch", 0, 100)
    unseal = ("unseal", 10, 60)
    acquire = ("session.acquire", 60, 64)
    program = [batch, unseal, acquire]
    host = [("result", 0, 200)]
    assert P.gap_name((20, 50), [program, host]) == "unseal"
    assert P.gap_name((61, 64), [program, host]) == "session.acquire"
    assert P.gap_name((55, 65), [program, host]) == "batch"
    assert P.gap_name((40, 90), [program, host]) == "batch"
    assert P.gap_name((120, 150), [program, host]) == "result"
    assert P.gap_name((300, 400), [program, host]) == "unattributed"
    assert P.gap_name((40, 90), [[unseal, acquire], host]) == "unseal"


def test_span_tiers_and_the_command_line(tmp_path, capsys):
    """Stages under a request name gaps first, the requests' queue spans
    next; roots (requests, the refill thread's prefetches) and unfinished
    spans name none. The command line reduces a trace and a span file."""
    spans = [
        {"name": "request", "kind": "request", "parent_id": None,
         "t0": 0.0, "t1": 1.0},
        {"name": "queue", "kind": "queue", "parent_id": 1,
         "t0": 0.0, "t1": 0.2},
        {"name": "batch", "kind": "batch", "parent_id": 1,
         "t0": 0.2, "t1": 0.9},
        {"name": "unseal", "kind": "crypto", "parent_id": 3,
         "t0": 0.2, "t1": None},
        {"name": "factors.prefetch", "kind": "session", "parent_id": None,
         "t0": 0.1, "t1": 0.3},
    ]
    assert P.span_tiers(spans) == [[("batch", 0.2, 0.9)],
                                   [("queue", 0.0, 0.2)]]
    path = tmp_path / "spans.jsonl"
    path.write_text("".join(json.dumps(s) + "\n" for s in spans)
                    + json.dumps({"truncated": True}) + "\n")
    assert P.read_spans(str(path))[-1] == {"truncated": True}
    assert P.main([str(FIXTURE), "--spans", str(path), "--device-plane",
                   CPU["device_plane"], "--op-line", CPU["op_line"]]) == 0
    out = capsys.readouterr().out.splitlines()
    assert [line.split(" ", 1)[0] for line in out] == \
        ["[phases]", "[clock]", "[gaps]"]
    # the fixture holds no anchor: its gaps go to the benchmark's
    # annotations, as the harness's own reduction names them
    gaps = json.loads(out[2].split(" ", 1)[1])
    red = T.reduce_file(str(FIXTURE), **CPU)
    assert [g[0] for g in gaps] == [g[0] for g in red.idle_gaps]


def test_factor_sync_share_reads_the_take_counters():
    """The share of factor sets a batch made itself, over the counters'
    part of the window; a program without the take counters gives no
    reading."""
    import run as R
    done = [types.SimpleNamespace(done=t, output=None) for t in (1.0, 2.0)]
    load = types.SimpleNamespace(t0=0.0, t1=10.0,
                                 done_in_window=lambda: done)
    run = types.SimpleNamespace(
        trace=object(), untraced_s=8.0, load=load, seconds=10.0, delta={},
        untraced_delta={"pool.factors_taken": 8, "pool.factors_sync": 2})
    read = R.load_module(R.reader_path("factor_sync_share.images"),
                         "factor_sync_share").read
    assert read(run) == pytest.approx(25.0)
    run.untraced_delta = {"pool.misses": 3, "pool.consumed": 4}
    assert read(run) is None
