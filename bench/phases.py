#!/usr/bin/env python3
"""Device seconds per program phase, and the program's spans on the
trace's clock, from one profiler trace of the program.

    python3 bench/phases.py <trace dir or .xplane.pb> [--spans <spans.jsonl>]

The program names what the device does inside its compiled programs by
``jax.named_scope`` (``repro.core.tracing.DEVICE_PHASES``). A scope rides
into the HLO as each operation's ``op_name`` metadata; the v5e's operation
events carry no scope of their own, so an operation's phase is read from
the HLO the trace carries (``hlo_op_names``): its module is the ``XLA
Modules`` event that contains it, its phase the innermost on its
``op_name``. Busy time in no phase is ``unattributed``.

``Tracer.clock_anchor`` writes the tracer's clock into the trace as
``tracer.clock`` annotations; their offsets lay the program's spans (a
``Tracer.dump_jsonl`` file) on the trace's clock, and each of the longest
idle gaps of the device is named by the innermost program stage that
covers it (``gap_name``), then by a request's ``queue`` span, then by the
benchmark's own annotations, else ``unattributed``.

Prints a ``[phases]`` line (device seconds and share of the busy time per
phase), a ``[clock]`` line (the anchors' offsets and their spread) and,
with spans, a ``[gaps]`` line.
"""
from __future__ import annotations

import argparse
import bisect
import json
import os
import re
import sys
from dataclasses import dataclass, field
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

sys.path.insert(0, str(Path(__file__).resolve().parent))

import trace_reduce  # noqa: E402
from trace_reduce import Interval, gaps, overlap, union  # noqa: E402

CLOCK_ANCHOR = "tracer.clock"       # core/tracing.py Tracer.clock_anchor
UNATTRIBUTED = "unattributed"

Named = Tuple[str, int, int]        # (name, start_ns, end_ns)


# -- the HLO a trace carries ------------------------------------------------
#
# ``ProfileData`` shows events but not the plane's event metadata, where
# the profiler keeps each executed module's HloProto (plane
# ``/host:metadata``, one metadata entry per module, named as the module's
# events are, e.g. ``jit__unseal_core(123)``). These few lines read the
# protobuf wire format for the handful of fields needed: XSpace.planes(1)
# > XPlane.name(2), event_metadata(4) > map value(2) > XEventMetadata
# .name(2), stats(5) > XStat.bytes_value(6) = HloProto > hlo_module(1) >
# computations(3) > instructions(2) > name(1), metadata(7) > op_name(2).

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if not b & 0x80:
            return out, i


def _fields(buf: bytes, lo: int, hi: int, want: int) -> List[Tuple[int, int]]:
    """(start, end) of every length-delimited field ``want`` of the
    message in buf[lo:hi]; other fields are skipped."""
    out, i = [], lo
    while i < hi:
        key, i = _varint(buf, i)
        wire = key & 7
        if wire == 0:
            _, i = _varint(buf, i)
        elif wire == 1:
            i += 8
        elif wire == 5:
            i += 4
        elif wire == 2:
            n, i = _varint(buf, i)
            if key >> 3 == want:
                out.append((i, i + n))
            i += n
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
    return out


def _text(buf: bytes, span: Sequence[Tuple[int, int]]) -> str:
    return buf[span[0][0]:span[0][1]].decode("utf-8", "replace") if span \
        else ""


def hlo_op_names(xspace: bytes) -> Dict[str, Dict[str, str]]:
    """{module event name: {HLO instruction name: op_name metadata}} for
    every module whose HLO the trace holds."""
    out: Dict[str, Dict[str, str]] = {}
    for plane in _fields(xspace, 0, len(xspace), 1):
        if _text(xspace, _fields(xspace, *plane, 2)) != "/host:metadata":
            continue
        for entry in _fields(xspace, *plane, 4):
            for meta in _fields(xspace, *entry, 2):
                name = _text(xspace, _fields(xspace, *meta, 2))
                ops: Dict[str, str] = {}
                for stat in _fields(xspace, *meta, 5):
                    for proto in _fields(xspace, *stat, 6):
                        for mod in _fields(xspace, *proto, 1):
                            for comp in _fields(xspace, *mod, 3):
                                for ins in _fields(xspace, *comp, 2):
                                    md = _fields(xspace, *ins, 7)
                                    op = (_text(xspace,
                                                _fields(xspace, *md[0], 2))
                                          if md else "")
                                    if op:
                                        ops[_text(xspace, _fields(
                                            xspace, *ins, 1))] = op
                if ops:
                    out[name] = ops
    return out


def phase_of(op_name: str, phases: Sequence[str]) -> Optional[str]:
    """The innermost device phase on an op's name-scope path, if any."""
    for part in reversed(op_name.split("/")):
        if part in phases:
            return part
    return None


def _instruction(event_name: str) -> str:
    """``%fusion.4 = s32[...] fusion(...)`` -> ``fusion.4``; a name that
    is no HLO text (the CPU's ``hlo_op`` events) stays as it is."""
    if event_name.startswith("%"):
        return event_name[1:].split(" ", 1)[0]
    return event_name


def _module_of(modules: Sequence[Named]):
    """Look up the module event (start, end, name) that contains an
    instant; ``modules`` sorted by start."""
    starts = [m[0] for m in modules]

    def find(t: int) -> Optional[str]:
        i = bisect.bisect_right(starts, t) - 1
        if i >= 0 and t < modules[i][1]:
            return modules[i][2]
        return None
    return find


# -- program spans on the trace's clock -------------------------------------

def gap_name(gap: Interval, tiers: Sequence[Sequence[Named]]) -> str:
    """Name a gap by the first tier with a span overlapping it: the
    innermost (shortest) span of that tier covering more than half of the
    gap, else the one covering most of it."""
    half = (gap[1] - gap[0]) / 2
    for tier in tiers:
        best, cover, inner, inner_len = None, 0, None, None
        for name, s, e in tier:
            c = overlap(gap, (s, e))
            if c > cover:
                best, cover = name, c
            if c > half and (inner_len is None or e - s < inner_len):
                inner, inner_len = name, e - s
        if inner is not None:
            return inner
        if best is not None:
            return best
    return UNATTRIBUTED


def span_tiers(spans: Sequence[dict]) -> List[List[Tuple[str, float, float]]]:
    """The program's finished spans (``Span.as_dict`` records) in the
    tiers ``gap_name`` tries, as (name, t0, t1) in tracer seconds: first
    the stages under a request (``batch``, ``unseal``, ``session.acquire``,
    ``infer``, ``factors.sync``, ``seal``, ...; they run on the engine's
    batcher and device threads), then the requests' ``queue`` spans.
    Roots (``request``, the refill thread's ``factors.prefetch``) name no
    gap."""
    done = [s for s in spans if s.get("t1") is not None and "name" in s]
    stages = [(s["name"], s["t0"], s["t1"]) for s in done
              if s.get("parent_id") is not None and s.get("kind") != "queue"]
    queue = [(s["name"], s["t0"], s["t1"]) for s in done
             if s.get("kind") == "queue"]
    return [stages, queue]


@dataclass
class Phases:
    window_s: float                   # first to last op of the device
    busy_s: float
    phase_seconds: Dict[str, float] = field(default_factory=dict)
    clock_offsets_ns: List[float] = field(default_factory=list)
    idle_gaps: List[Tuple[str, float]] = field(default_factory=list)

    @property
    def clock_drift_ns(self) -> Optional[float]:
        offs = self.clock_offsets_ns
        return max(offs) - min(offs) if offs else None


def reduce_phases(profile, hlo: Dict[str, Dict[str, str]],
                  phases: Sequence[str], *,
                  device_plane: str = r"^/device:TPU:\d+$",
                  op_line: str = r"^XLA Ops$",
                  module_line: str = r"^XLA Modules$",
                  host_plane: str = r"^/host:CPU$",
                  tiers: Sequence[Sequence[Tuple[str, float, float]]] = (),
                  n_gaps: int = 10) -> Phases:
    """``profile``: a ``jax.profiler.ProfileData``; ``hlo``: its
    ``hlo_op_names``. Reads the first plane matching ``device_plane`` that
    holds operations (the ``op_line`` events): each operation's module is
    the ``module_line`` event that contains it or, where the event says,
    its ``hlo_module`` and ``program_id`` stats (the CPU's). ``tiers``:
    program spans (``span_tiers``), laid on the trace's clock by the
    ``tracer.clock`` anchors of the ``host_plane`` planes, and left out
    where the trace holds none."""
    dev_rx, line_rx = re.compile(device_plane), re.compile(op_line)
    mod_rx, host_rx = re.compile(module_line), re.compile(host_plane)
    ops: List[Interval] = []
    by_phase: Dict[str, List[Interval]] = {}
    host: List[Named] = []
    offsets: List[float] = []
    for plane in profile.planes:
        if host_rx.search(plane.name):
            for line in plane.lines:
                for ev in line.events:
                    s = int(ev.start_ns)
                    if ev.name in trace_reduce.HOST_SPANS:
                        host.append((ev.name, s, s + int(ev.duration_ns)))
                    elif ev.name == CLOCK_ANCHOR:
                        st = dict(ev.stats)
                        if "tracer_ns" in st:
                            offsets.append(s - float(st["tracer_ns"]))
        if ops or not dev_rx.search(plane.name):
            continue
        modules = sorted((s, e, n) for line in plane.lines
                         if mod_rx.search(line.name)
                         for n, s, e in trace_reduce._events(line))
        module_at = _module_of(modules)
        cache: Dict[Tuple[Optional[str], str], str] = {}
        for line in plane.lines:
            if not line_rx.search(line.name):
                continue
            for ev in line.events:
                s = int(ev.start_ns)
                e = s + int(ev.duration_ns)
                ops.append((s, e))
                mod, op = module_at(s), _instruction(ev.name)
                if mod is None:
                    st = dict(ev.stats)
                    if "hlo_module" in st:
                        mod = f"{st['hlo_module']}({st.get('program_id')})"
                        op = str(st.get("hlo_op", op))
                key = (mod, op)
                if key not in cache:
                    cache[key] = phase_of(hlo.get(mod, {}).get(op, ""),
                                          phases) or UNATTRIBUTED
                by_phase.setdefault(cache[key], []).append((s, e))
    if not ops:
        raise ValueError("the trace holds no device operation")
    busy = union(ops)
    lo, hi = busy[0][0], busy[-1][1]
    busy_s = sum(e - s for s, e in busy) / 1e9
    out: Dict[str, float] = {}
    named: List[Interval] = []
    for ph, ivs in by_phase.items():
        if ph != UNATTRIBUTED:
            out[ph] = sum(e - s for s, e in union(ivs)) / 1e9
            named += ivs
    out[UNATTRIBUTED] = max(
        0.0, busy_s - sum(e - s for s, e in union(named)) / 1e9)
    laid: List[List[Named]] = []
    if offsets:
        off = sum(offsets) / len(offsets)
        laid = [[(n, int(t0 * 1e9 + off), int(t1 * 1e9 + off))
                 for n, t0, t1 in tier] for tier in tiers]
    laid.append(host)
    idle = sorted(gaps(busy, lo, hi), key=lambda g: g[0] - g[1])
    return Phases(window_s=(hi - lo) / 1e9, busy_s=busy_s,
                  phase_seconds=out, clock_offsets_ns=offsets,
                  idle_gaps=[(gap_name(g, laid), (g[1] - g[0]) / 1e9)
                             for g in idle[:n_gaps]])


def reduce_file(path: str, phases: Sequence[str],
                spans: Sequence[dict] = (), **kw) -> Phases:
    from jax.profiler import ProfileData
    with open(path, "rb") as f:
        hlo = hlo_op_names(f.read())
    return reduce_phases(ProfileData.from_file(path), hlo, phases,
                         tiers=span_tiers(spans), **kw)


def read_spans(path: str) -> List[dict]:
    """The span records of a ``Tracer.dump_jsonl`` file."""
    with open(path) as f:
        return [json.loads(line) for line in f if line.strip()]


def lines(red: Phases, with_gaps: bool) -> List[str]:
    table = {k: [v, 100.0 * v / red.busy_s if red.busy_s else None]
             for k, v in sorted(red.phase_seconds.items(),
                                key=lambda kv: -kv[1])}
    out = [f"[phases] busy {red.busy_s} s of {red.window_s} s; phase: "
           f"[device s, % of busy] {json.dumps(table)}",
           f"[clock] anchors {len(red.clock_offsets_ns)}, offsets ns "
           f"{red.clock_offsets_ns}, drift ns {red.clock_drift_ns}"]
    if with_gaps:
        out.append(f"[gaps] {json.dumps(red.idle_gaps)}")
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("trace", help="a trace directory or an .xplane.pb")
    ap.add_argument("--spans", default=None,
                    help="the program's spans (Tracer.dump_jsonl)")
    ap.add_argument("--device-plane", default=r"^/device:TPU:\d+$")
    ap.add_argument("--op-line", default=r"^XLA Ops$")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent / "src"))
    from repro.core.tracing import DEVICE_PHASES
    path = (args.trace if os.path.isfile(args.trace)
            else trace_reduce.find_xplane(args.trace))
    spans = read_spans(args.spans) if args.spans else ()
    red = reduce_file(path, DEVICE_PHASES, spans,
                      device_plane=args.device_plane, op_line=args.op_line)
    for line in lines(red, bool(spans)):
        print(line)
    return 0


if __name__ == "__main__":
    sys.exit(main())
