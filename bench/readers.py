"""Arithmetic the metric readers (``bench/metrics/<metric>.py``) share.

A reader returns None where its run holds nothing to read; the harness
then leaves the metric out of the line. No reader returns 0 for a share of
a peak or a roofline it could not measure.
"""
from __future__ import annotations

import math
from typing import Optional, Sequence

import trace_reduce


def quantile(values: Sequence[float], q: float) -> Optional[float]:
    """Nearest-rank quantile; None for no values."""
    vals = sorted(values)
    if not vals:
        return None
    return vals[min(len(vals) - 1, max(0, math.ceil(q * len(vals)) - 1))]


def span(run):
    """(counter deltas, end, seconds) of the part of the window that a
    counter or span metric reads: all of it, or in a traced run the part
    before the profiler started (it records the window's last seconds)."""
    if run.trace is None or run.untraced_s <= 0:
        return run.delta, run.load.t1, run.seconds
    return (run.untraced_delta, run.load.t0 + run.untraced_s,
            run.untraced_s)


def units_per_s(run, unit: str) -> Optional[float]:
    """Work units (images) of the requests completed and opened inside
    the window (or its untraced part), over its length."""
    if run.model.unit != unit:
        return None
    _, end, seconds = span(run)
    done = [s for s in run.load.done_in_window() if s.done < end]
    return sum(run.model.units(s.output) for s in done) / seconds


def program_spans(run, name: str):
    """The program's finished spans called ``name`` that started inside
    the part of the window that ``span`` reads."""
    _, end, _ = span(run)
    lo, hi = run.load.t0 - run.span_epoch, end - run.span_epoch
    return [s for s in run.spans
            if s.name == name and s.t1 is not None and lo <= s.t0 < hi]


def share(num: float, den: float) -> Optional[float]:
    return None if den <= 0 else 100.0 * num / den


def mfu(run, unit: str) -> Optional[float]:
    """Plain forward FLOPs of the completed work over the bf16 peak."""
    rate = units_per_s(run, unit)
    if rate is None or "bf16_flops" not in run.peaks:
        return None
    return share(rate * run.model.flops_per_unit, run.peaks["bf16_flops"])


# Pallas kernels that compute field matmuls, by their names in the device
# trace (the jitted wrappers of kernels/limb_matmul/ops.py): the plain field
# matmul (u = r·W_q, W_q·s) and the fused blinded one (blind-encode pass and
# matmul with the unblinding epilogue). The Freivalds fold is not one.
FIELD_MATMUL_KERNELS = (r"^%_fused_blinded_matmul_jit\b",
                        r"^%_field_matmul_jit\b")


def field_matmul_roofline(run) -> Optional[float]:
    """Least time the window's field matmuls could take on the chip, from
    their shapes, over the device time of the field-matmul kernels."""
    if run.trace is None or not run.peaks:
        return None
    t = run.trace.seconds_matching(FIELD_MATMUL_KERNELS)
    if t <= 0:
        return None
    least = 0.0
    for kind, M, K, N, count in run.model.field_work(run.trace_delta,
                                                     run.buckets):
        work = (trace_reduce.fused_blinded_matmul_work if kind == "fused"
                else trace_reduce.field_matmul_work)
        ops, nbytes = work(M, K, N)
        least += count * trace_reduce.roofline_seconds(
            ops, nbytes, run.peaks["int8_ops"], run.peaks["hbm_bytes_per_s"])
    return share(least, t)


def idle_share(run) -> Optional[float]:
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share
