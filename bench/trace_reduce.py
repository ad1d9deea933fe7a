"""Reduce a profiler trace (``.xplane.pb``) to device and kernel numbers,
and the work functions the roofline divides them by.

One reduction for every cell:

- ``busy_intervals``: the union of the device's operation intervals;
- ``Reduction.busy_s`` / ``window_s`` / ``idle_share``;
- ``Reduction.op_seconds``: device seconds per operation name;
- ``Reduction.idle_gaps``: the longest gaps between device operations,
  each named by the host annotation that covers most of it
  (``TraceAnnotation`` spans of the benchmark: ``submit``, ``result``,
  ``client_open``), or ``unattributed``.

The work functions count the operation, not its implementation: a field
matmul of (M, K, N) is 2·M·K·N integer operations, and its bytes are its
logical operands and result at their stated dtypes, however many limb
products or reductions a kernel spends on it.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

# the benchmark's own host annotations (loadgen.py)
HOST_SPANS = ("submit", "result", "client_open")

Interval = Tuple[int, int]          # [start_ns, end_ns)


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals: Iterable[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for s, e in sorted(intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def gaps(busy: Sequence[Interval], lo: int, hi: int) -> List[Interval]:
    """Idle intervals of [lo, hi) not covered by ``busy`` (a union)."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [g for g in out if g[1] > g[0]]


def overlap(a: Interval, b: Interval) -> int:
    return max(0, min(a[1], b[1]) - max(a[0], b[0]))


@dataclass
class Reduction:
    window_s: float
    busy_s: float                 # mean over the devices traced
    n_devices: int
    op_seconds: Dict[str, float] = field(default_factory=dict)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def seconds_matching(self, patterns: Sequence[str]) -> float:
        rx = [re.compile(p) for p in patterns]
        return sum(t for name, t in self.op_seconds.items()
                   if any(r.search(name) for r in rx))

    def top_ops(self, n: int = 10) -> List[List]:
        ops = sorted(self.op_seconds.items(), key=lambda kv: -kv[1])
        return [[k, v] for k, v in ops[:n]]


def _events(line):
    for ev in line.events:
        s = int(ev.start_ns)
        yield ev.name, s, s + int(ev.duration_ns)


def reduce_profile(profile, *, device_plane: str = r"^/device:TPU:\d+$",
                   op_line: str = r"^XLA Ops$",
                   host_plane: str = r"^/host:CPU$",
                   window: Optional[Interval] = None,
                   n_gaps: int = 10) -> Reduction:
    """``profile``: a ``jax.profiler.ProfileData``. Device operations are
    the events of the lines matching ``op_line`` on the planes matching
    ``device_plane``. The traced window is ``window`` (ns), or else the
    span from the first to the last device operation: a device that runs
    out of event buffer stops recording, and a window measured by the host
    would then count the unrecorded time as idle."""
    dev_rx, line_rx = re.compile(device_plane), re.compile(op_line)
    host_rx = re.compile(host_plane)
    per_device: List[List[Interval]] = []
    op_seconds: Dict[str, float] = {}
    host: List[Tuple[str, int, int]] = []
    for plane in profile.planes:
        is_dev = bool(dev_rx.search(plane.name))
        is_host = bool(host_rx.search(plane.name))
        dev_ints: List[Interval] = []
        for line in plane.lines:
            take_ops = is_dev and bool(line_rx.search(line.name))
            for name, s, e in _events(line):
                if take_ops:
                    dev_ints.append((s, e))
                    op_seconds[name] = op_seconds.get(name, 0.0) + (e - s) / 1e9
                elif is_host and name in HOST_SPANS:
                    host.append((name, s, e))
        if dev_ints:
            per_device.append(union(dev_ints))
    if not per_device:
        raise ValueError("the trace holds no device operation")
    if window is not None:
        lo, hi = window
    else:
        lo = min(u[0][0] for u in per_device)
        hi = max(u[-1][1] for u in per_device)
    window_s = (hi - lo) / 1e9
    busy = [sum(overlap(iv, (lo, hi)) for iv in u) / 1e9 for u in per_device]
    # gaps of the first device, attributed to the host span covering most
    idle = sorted(gaps(per_device[0], lo, hi), key=lambda g: g[0] - g[1])
    named = []
    for g in idle[:n_gaps]:
        best, cover = "unattributed", 0
        for name, s, e in host:
            c = overlap(g, (s, e))
            if c > cover:
                best, cover = name, c
        named.append((best, (g[1] - g[0]) / 1e9))
    return Reduction(window_s=window_s, busy_s=sum(busy) / len(busy),
                     n_devices=len(per_device), op_seconds=op_seconds,
                     idle_gaps=named)


def reduce_file(path: str, **kw) -> Reduction:
    from jax.profiler import ProfileData
    return reduce_profile(ProfileData.from_file(path), **kw)


# -- work of an operation, from its shapes ----------------------------------

FIELD_BYTES = 4      # a field element is stated as int32


def field_matmul_work(M: int, K: int, N: int) -> Tuple[int, int]:
    """(integer ops, bytes) of y = x·W mod p, x (M, K), W (K, N), all int32."""
    return 2 * M * K * N, FIELD_BYTES * (M * K + K * N + M * N)


def fused_blinded_matmul_work(M: int, K: int, N: int) -> Tuple[int, int]:
    """(integer ops, bytes) of the fused blinded matmul: float32
    activations (M, K) and pads r (M, K) in, field weights (K, N) and
    unblinding factors u (M, N) in, float32 result (M, N) out."""
    return 2 * M * K * N, 4 * (2 * M * K + K * N + 2 * M * N)


def roofline_seconds(ops: float, nbytes: float, peak_ops: float,
                     peak_bw: float) -> float:
    """Least time the chip could take: the slower of compute and memory."""
    return max(ops / peak_ops, nbytes / peak_bw)


def conv_flops(h: int, w: int, cin: int, cout: int, k: int = 3) -> int:
    return 2 * h * w * k * k * cin * cout


def vgg_forward_flops(layers: Sequence[str], image_size: int, channels: int,
                      num_classes: int) -> int:
    """FLOPs of one image through the plain VGG forward (convs 3x3 SAME,
    2x2 pools, dense layers); pools and activations are not counted."""
    h = w = image_size
    c, flat, total = channels, None, 0
    for spec in layers:
        if spec.startswith("conv"):
            n = int(spec[4:])
            total += conv_flops(h, w, c, n)
            c = n
        elif spec == "pool":
            h, w = h // 2, w // 2
        else:
            n = num_classes if spec == "logits" else int(spec[2:])
            d_in = flat if flat is not None else h * w * c
            total += 2 * d_in * n
            flat = n
    return total

