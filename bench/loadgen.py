"""The one load generator: drives sealed requests into a ServingEngine.

A traffic mix is a data file (``bench/traffic/<mix>.json``) read here:

- ``{"loop": "closed", "clients": N}``: N clients, each with one request
  outstanding; a client sends its next request as soon as it has opened
  the previous response.
- ``{"loop": "open", "arrivals": "poisson", "rate_per_s": R}``: requests
  are due on a schedule of exponential gaps drawn from the seed, sent
  whether or not earlier ones have finished.
- ``"warmup_s": W`` (either loop): the load runs W seconds before the
  window opens, so that the window sees the steady state (the session
  pool drained to its refill rate) and not the start of the load.

Every request is timed from when it was due to when its client opened the
response. Two threads do all the work: a sender (open loop only) and the
collector, which opens responses and, in a closed loop, sends the next
request. Sealed requests are made before the window; a request of the
pool is sent again only when its previous copy is not in flight.
"""
from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import numpy as np

DRAIN_S = 60.0          # how long to wait past the window for answers


@dataclass
class Sent:
    """One request as the client saw it."""
    idx: int                  # index into the sealed pool
    rid: int
    due: float                # perf_counter seconds
    submitted: float = 0.0
    done: Optional[float] = None
    ok: bool = False
    error: Optional[str] = None
    output: Any = None


@dataclass
class LoadResult:
    t0: float
    t1: float                 # window end
    sent: List[Sent] = field(default_factory=list)
    lateness_s: List[float] = field(default_factory=list)  # send - due

    def due_in_window(self) -> List[Sent]:
        return [s for s in self.sent if self.t0 <= s.due < self.t1]

    def done_in_window(self) -> List[Sent]:
        return [s for s in self.sent if s.ok and s.done is not None
                and self.t0 <= s.done < self.t1]

    def open_in_window(self) -> List[Sent]:
        """Requests sent in the window, or in flight when it opened."""
        return [s for s in self.sent if s.submitted < self.t1
                and (s.done is None or s.done >= self.t0)]


def arrival_offsets(traffic: Dict[str, Any], seconds: float,
                    rng: np.random.Generator) -> np.ndarray:
    """Due times (seconds after the window opens) of an open loop."""
    if traffic.get("arrivals") != "poisson":
        raise ValueError(f"unknown arrivals {traffic.get('arrivals')!r}")
    rate = float(traffic["rate_per_s"])
    n = int(rate * seconds * 2 + 64)
    t = np.cumsum(rng.exponential(1.0 / rate, size=n))
    return t[t < seconds]


class LoadGenerator:
    """Sends ``pool`` (a list of ``(Request, open_fn)``) into ``engine``."""

    def __init__(self, engine, model: str, pool: List[Any],
                 open_fn: Callable[[Any, Any], Any]):
        self.engine = engine
        self.model = model
        self.pool = pool
        self.open_fn = open_fn
        self._next = 0
        self._busy = set()
        self._q: "queue.Queue" = queue.Queue()
        self._lock = threading.Lock()

    def _take(self) -> int:
        with self._lock:
            for _ in range(len(self.pool)):
                i = self._next
                self._next = (self._next + 1) % len(self.pool)
                if i not in self._busy:
                    self._busy.add(i)
                    return i
        raise RuntimeError("every pooled request is in flight; "
                           "the pool is smaller than the load")

    def _send(self, due: float, out: LoadResult) -> None:
        i = self._take()
        req = self.pool[i][0]
        s = Sent(idx=i, rid=req.rid, due=due)
        with jax.profiler.TraceAnnotation("submit"):
            s.submitted = time.perf_counter()
            fut = self.engine.submit(self.model, req)
        with self._lock:
            out.sent.append(s)
            out.lateness_s.append(s.submitted - due)
        fut.add_done_callback(lambda f, s=s: self._q.put((s, f)))

    def _collect(self, s: Sent, fut) -> None:
        try:
            resp = fut.result()
            if not resp.ok:
                s.error = resp.error or "not ok"
            else:
                with jax.profiler.TraceAnnotation("client_open"):
                    s.output = self.open_fn(self.pool[s.idx], resp)
                s.ok = True
        except Exception as exc:  # noqa: BLE001 — a failed request is a
            s.error = repr(exc)  # count, not a crash of the generator
        s.done = time.perf_counter()
        with self._lock:
            self._busy.discard(s.idx)

    def run(self, traffic: Dict[str, Any], seconds: float,
            rng: np.random.Generator,
            on_window: Optional[Callable[[str], None]] = None,
            marks: Sequence[Tuple[float, str]] = ()) -> LoadResult:
        """Load for the traffic's ``warmup_s``, then measure for
        ``seconds``; ``on_window("open"/"close")`` is called at the
        window's edges (counter snapshots, the profiler), and
        ``on_window(name)`` once the window is ``offset`` seconds old, for
        each ``(offset, name)`` of ``marks``."""
        closed = traffic["loop"] == "closed"
        if not closed and traffic["loop"] != "open":
            raise ValueError(f"unknown loop {traffic['loop']!r}")
        warmup = float(traffic.get("warmup_s", 0.0))
        offsets = (None if closed
                   else arrival_offsets(traffic, warmup + seconds, rng))
        start = time.perf_counter()
        t0 = start + warmup
        out = LoadResult(t0=t0, t1=t0 + seconds)
        on_window = on_window or (lambda edge: None)
        pending = sorted([(t0, "open")]
                         + [(t0 + off, name) for off, name in marks],
                         key=lambda mark: mark[0])
        sender = None
        if closed:
            for _ in range(int(traffic["clients"])):
                self._send(start, out)
        else:
            def send_all():
                for off in offsets:
                    due = start + float(off)
                    delay = due - time.perf_counter()
                    if delay > 0:
                        time.sleep(delay)
                    self._send(due, out)
            sender = threading.Thread(target=send_all, name="loadgen-send")
            sender.start()
        closed_window = False
        expected = None if closed else len(offsets)
        deadline = out.t1 + DRAIN_S
        n_done = 0
        while True:
            now = time.perf_counter()
            while pending and now >= pending[0][0]:
                on_window(pending.pop(0)[1])
                now = time.perf_counter()
            if not closed_window and now >= out.t1:
                closed_window = True
                on_window("close")
                deadline = time.perf_counter() + DRAIN_S
            with self._lock:
                n_sent = len(out.sent)
            if closed_window and n_done >= n_sent and (
                    expected is None or n_sent >= expected):
                break
            if now > deadline:
                break
            wait = (out.t1 - now) if not closed_window else deadline - now
            if pending:
                wait = min(wait, pending[0][0] - now)
            try:
                with jax.profiler.TraceAnnotation("result"):
                    s, fut = self._q.get(timeout=max(1e-3, min(wait, 0.5)))
            except queue.Empty:
                continue
            self._collect(s, fut)
            n_done += 1
            if closed and time.perf_counter() < out.t1:
                self._send(time.perf_counter(), out)
        if sender is not None:
            sender.join(timeout=DRAIN_S)
        return out
