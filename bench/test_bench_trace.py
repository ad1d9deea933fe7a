"""The trace reduction and the work functions, on a small trace recorded
on a CPU (``testdata/cpu_small.xplane.pb``: three jitted matmuls, each
under ``submit`` and ``client_open`` annotations). Stays off the TPU."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parent
sys.path.insert(0, str(BENCH))

import trace_reduce as T  # noqa: E402

FIXTURE = BENCH / "testdata" / "cpu_small.xplane.pb"
CPU = dict(device_plane=r"^/host:CPU$", op_line=r"^tf_XLAPjRtCpuClient")


def test_union_and_gaps():
    assert T.union([(5, 9), (0, 3), (2, 4), (9, 10), (12, 12)]) == \
        [(0, 4), (5, 10)]
    busy = T.union([(2, 4), (6, 8)])
    assert T.gaps(busy, 0, 10) == [(0, 2), (4, 6), (8, 10)]
    assert T.gaps(busy, 3, 7) == [(4, 6)]
    assert T.gaps([], 0, 5) == [(0, 5)]


def test_reduction_of_a_recorded_trace():
    red = T.reduce_file(str(FIXTURE), **CPU)
    assert red.n_devices == 1
    assert 0 < red.busy_s < red.window_s
    assert 0 < red.idle_share < 1
    assert red.seconds_matching([r"^dot_general"]) > 0
    ops = T.union(iv for line in _planes() for iv in line)
    assert red.busy_s == pytest.approx(sum(e - s for s, e in ops) / 1e9)
    names = {g[0] for g in red.idle_gaps}
    assert names <= {"submit", "result", "client_open", "unattributed"}
    assert "submit" in names or "client_open" in names
    top = red.top_ops(3)
    assert top == sorted(top, key=lambda kv: -kv[1])


def _planes():
    from jax.profiler import ProfileData
    import re
    pd = ProfileData.from_file(str(FIXTURE))
    out = []
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            if re.search(CPU["op_line"], line.name):
                out.append([(int(e.start_ns), int(e.start_ns + e.duration_ns))
                            for e in line.events])
    return out


def test_a_trace_with_no_device_op_is_refused():
    with pytest.raises(ValueError):
        T.reduce_file(str(FIXTURE))      # no /device:TPU plane in it


def test_window_is_respected():
    red = T.reduce_file(str(FIXTURE), **CPU)
    full = T.reduce_file(str(FIXTURE), window=(0, 10 ** 12), **CPU)
    assert full.window_s == pytest.approx(1000.0)
    assert full.busy_s == pytest.approx(red.busy_s, rel=1e-6)


def test_field_matmul_work_counts_the_operation():
    assert T.field_matmul_work(2, 3, 4) == (48, 4 * (6 + 12 + 8))
    ops, nbytes = T.fused_blinded_matmul_work(2, 3, 4)
    assert ops == 48 and nbytes == 4 * (12 + 12 + 16)
    # bound by memory when K is small, by compute when it is large
    small = T.roofline_seconds(*T.field_matmul_work(50176, 27, 64),
                               393e12, 819e9)
    assert small == pytest.approx(4 * (50176 * 27 + 27 * 64 + 50176 * 64)
                                  / 819e9)
    big = T.roofline_seconds(*T.field_matmul_work(8192, 8192, 8192),
                             393e12, 819e9)
    assert big == pytest.approx(2 * 8192 ** 3 / 393e12)


def test_model_flops():
    vgg16 = ("conv64 conv64 pool conv128 conv128 pool conv256 conv256 "
             "conv256 pool conv512 conv512 conv512 pool conv512 conv512 "
             "conv512 pool fc4096 fc4096 logits").split()
    # 15.47 G multiply-adds per 224x224 image (Simonyan & Zisserman)
    assert T.vgg_forward_flops(vgg16, 224, 3, 1000) / 2 == \
        pytest.approx(15.47e9, rel=0.01)
    # pools are not counted; a dense layer after a pool sees the pooled map
    assert T.vgg_forward_flops(["conv8", "pool", "fc4", "logits"], 4, 1,
                               3) == 2 * (16 * 9 * 8 + 32 * 4 + 4 * 3)


def test_field_matmul_roofline_reads_the_named_kernels():
    import types
    import readers
    red = T.Reduction(window_s=2.0, busy_s=1.0, n_devices=1, op_seconds={
        "%_fused_blinded_matmul_jit.10 = f32[...] custom-call(...)": 0.03,
        "%_field_matmul_jit.1 = s32[...] custom-call(...)": 0.01,
        "%_field_fold_jit.4 = s32[...] custom-call(...)": 5.0,
        "%while.1 = (...)": 9.0})
    model = types.SimpleNamespace(field_work=lambda delta, buckets: [
        ("fused", 4096, 1024, 128, delta["n"]),
        ("plain", 4096, 1024, 128, delta["n"])])
    peaks = {"int8_ops": 393e12, "hbm_bytes_per_s": 819e9}
    run = types.SimpleNamespace(trace=red, trace_delta={"n": 10},
                                buckets=[4], model=model, peaks=peaks)
    least = 10 * sum(T.roofline_seconds(*w(4096, 1024, 128), 393e12, 819e9)
                     for w in (T.fused_blinded_matmul_work,
                               T.field_matmul_work))
    assert readers.field_matmul_roofline(run) == pytest.approx(
        100 * least / 0.04)
    red.op_seconds = {"%while.1 = (...)": 9.0}
    assert readers.field_matmul_roofline(run) is None
