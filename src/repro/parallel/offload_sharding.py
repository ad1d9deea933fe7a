"""Sharded blinded offload: one field matmul across many untrusted devices.

The Slalom protocol offloads ``y_b = (x_b @ W_q) mod p`` to ONE untrusted
accelerator. DarKnight (PAPERS.md) shows the same blinding construction
distributes: this module shards each blinded matmul across a
``runtime/devices.DevicePool`` and is the dispatch half of the multi-device
plane (the pool is the health half). Two shard geometries
(``core/plan.ShardPolicy``):

- **rows**: the blinded operand row-shards over the batch/token dim —
  shard j is rows [lo_j, hi_j) of ``x_b``; results concatenate. Each
  device sees a *slice* of the one-time-padded tensor (still uniform over
  Z_p — a slice of a pad is a pad), and the pool's aggregate throughput
  bounds the op, not one part's.
- **shares**: additive secret sharing — ``x_b = (Σ_j x_j) mod p`` with
  every proper subset of shares independently uniform, so **no single
  device ever holds the full blinded tensor** (defense in depth if a
  session pad were ever mismanaged: reconstructing ``x_b`` needs ALL
  shares). Each device multiplies its full-shape share; results sum
  mod p. Work is replicated n×, which is the price of the stronger
  non-collusion guarantee.

Both geometries are linear in ``x``, so the assembled result is
**bit-identical** to the single-device matmul — the executor's logits do
not change when a pool is attached (tests/test_offload_sharding.py).

**Shard-local Freivalds.** Every shard is checked independently with its
own fold vectors ``(s_j, ws_j = W_q @ s_j)`` (core/integrity.py
``shard_fold_stream``; prefetched per session by core/precompute.py via the
SessionPool ring): ``y_j @ s_j ≡ x_j @ ws_j (mod p)``. A corrupt result
therefore indicts a *device*, not the op — only that shard is re-dispatched
to another healthy device (the honest devices' work is never recomputed),
the pool records the failure against the slot (quarantine/probation), and
only when every device is exhausted does the enclave compute the shard
itself. Shards are ALWAYS checked when a plane is active (the adaptive
adversary of runtime/faults.py, which corrupts only unchecked ops, is
structurally neutralized here).

**Straggler hedging.** Shard wall times feed a ``runtime/straggler.py``
``StepWatchdog``; once warmed, a shard exceeding ``deadline_factor`` × the
P50 is duplicated onto the fastest spare healthy device and the first
*verified* result wins (pure duplication — resending the same blinded
shard reveals nothing new to the spare device). The loser's latency still
feeds its EWMA so placement learns to avoid chronic stragglers.

**Liveness recovery ladder (DESIGN.md §12).** The integrity ladder above
handles devices that return *wrong* results; this plane also survives
devices that return *none*:

- **exception containment**: a dispatch that raises (crash, cancelled
  queue) resolves as a liveness failure of that DEVICE — the exception
  never propagates into the batch, and only that shard re-dispatches;
- **hard per-dispatch timeout**: ``liveness.timeout_factor`` × the same
  watchdog P50 the hedge uses (with a floor, and a ``cold_timeout_s``
  fallback before warmup). A dispatch past it is abandoned — the slot's
  wedged queue is cut loose (``DeviceSlot.abandon``) so a hung worker
  never blocks later probes — and the shard re-dispatches;
- **exponential backoff with jitter** between liveness re-dispatches of
  one shard (transient flake storms de-synchronize instead of stampeding);
- **per-device circuit breaker**: ``breaker_after`` consecutive liveness
  failures open the slot's breaker (no traffic); after a cooldown it
  half-opens and ONE probe shard is routed — a verified success closes
  it, failure re-opens with doubled cooldown. Distinct from the
  integrity quarantine; the two compose (a slot serves only when neither
  indicts it).

As with integrity, the enclave computes the shard itself when every
eligible device is exhausted — so **every submitted matmul resolves**
under any liveness fault schedule, and the assembled result stays
bit-identical (recovered shards are recomputed from the same operands).
In ``shares`` mode the confinement rule still applies: a crashed or
timed-out share goes straight to the enclave, never to a second device.

Host-side control flow (retry, hedging, health) cannot live inside a jit
trace — an executor with a pool runs its plan interpreter eagerly
(core/origami.py), which PR 1's kernels make bit-identical to the jitted
trace. Ops traced under ``lax.scan`` stay on the single-device path (the
same per-op addressability limit as precompute/verification).
"""
from __future__ import annotations

import dataclasses
import random
import threading
import time
from concurrent.futures import FIRST_COMPLETED, wait
from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.core import blinding as B
from repro.core import integrity as IG
from repro.core import tracing
from repro.core.plan import SHARD_MODES
from repro.runtime import faults as FT
from repro.kernels.limb_matmul.ops import field_matmul
from repro.kernels.limb_matmul.ref import P
from repro.runtime.devices import DevicePool, DeviceSlot
from repro.runtime.straggler import StepWatchdog, WatchdogConfig

# fold_in domains: additive-share masks and per-shard fault keys live in
# their own sub-spaces, disjoint from blinding/verify/fault streams
SHARE_DOMAIN = 0x5A8E
_SHARD_FAULT = 0x51


@dataclasses.dataclass
class LivenessConfig:
    """Liveness-ladder knobs (per plane; DESIGN.md §12 tabulates them).

    The hard timeout shares the StepWatchdog baseline with hedging:
    ``timeout_factor × P50`` once the window is warm (floored — a
    sub-millisecond P50 must not turn scheduler jitter into abandons),
    ``cold_timeout_s`` before that. Backoff sleeps
    ``base × factor^attempt × (1 + jitter·u)`` between liveness
    re-dispatches of one shard, u deterministic in (op, shard, attempt).
    """
    timeout_factor: float = 8.0
    timeout_floor_s: float = 0.25
    cold_timeout_s: float = 10.0
    backoff_base_s: float = 0.005
    backoff_factor: float = 2.0
    backoff_max_s: float = 0.25
    backoff_jitter: float = 0.5


@dataclasses.dataclass
class ShardReport:
    """Per-infer outcome of the sharded plane (host-side counters)."""
    ops: int = 0                    # sharded matmuls dispatched
    dispatches: int = 0             # shard -> device submissions (all)
    checks: int = 0                 # shard-local Freivalds checks run
    failures: int = 0               # checks that mismatched
    retries: int = 0                # single-shard re-dispatches
    hedges: int = 0                 # straggler duplicates launched
    enclave_shards: int = 0         # shards the enclave computed itself
    probes: int = 0                 # probation probes routed
    # liveness ladder (DESIGN.md §12)
    crashes: int = 0                # dispatches that raised (contained)
    timeouts: int = 0               # dispatches abandoned past the deadline
    backoffs: int = 0               # backoff sleeps between re-dispatches
    breaker_probes: int = 0         # half-open liveness probes routed

    @property
    def flagged(self) -> bool:
        """A device misbehaved (even though every shard was recovered)."""
        return self.failures > 0

    def add(self, other: "ShardReport") -> None:
        for f in dataclasses.fields(self):
            setattr(self, f.name,
                    getattr(self, f.name) + getattr(other, f.name))


def row_spans(t: int, n: int) -> List[Tuple[int, int]]:
    """Balanced contiguous row ranges — shard j owns [lo_j, hi_j).

    Static in (t, n): the split never depends on device health, so the
    assembled result (and the per-shard fold material) is identical
    whichever devices end up computing the shards."""
    base, extra = divmod(t, n)
    spans, lo = [], 0
    for j in range(n):
        hi = lo + base + (1 if j < extra else 0)
        spans.append((lo, hi))
        lo = hi
    return spans


def additive_shares(x_field: jax.Array, session_key: jax.Array,
                    op_index: int, step: int, n: int) -> List[jax.Array]:
    """Split ``x_field`` into n additive shares over Z_p.

    Shares 0..n-2 are fresh uniform masks drawn from the SHARE_DOMAIN
    stream (enclave-private, never reused across (session, op, step));
    the last share is the residual. Any proper subset is jointly uniform —
    reconstructing the blinded tensor needs every share."""
    root = B.stream_key(jax.random.fold_in(session_key, SHARE_DOMAIN),
                        op_index, step)
    shares, acc = [], None
    for j in range(n - 1):
        m = B.blinding_stream(jax.random.fold_in(root, j), x_field.shape)
        shares.append(m)
        acc = m if acc is None else jnp.mod(acc + m, P)
    resid = x_field if acc is None else jnp.mod(x_field - acc + P, P)
    shares.append(resid)
    return shares


@dataclasses.dataclass
class _ShardTask:
    index: int                      # shard id (static)
    op_index: int                   # the blinded op this shard belongs to
    x: jax.Array                    # the operand this shard's device gets
    s: jax.Array                    # fold vectors (d_out, k)
    ws: jax.Array                   # (d_in, k) = W_q @ s mod p
    fault_key: jax.Array


class OffloadPlane:
    """Dispatches blinded field matmuls across a DevicePool."""

    def __init__(self, pool: DevicePool, *, mode: str = "rows",
                 hedging: bool = True,
                 watchdog: Optional[StepWatchdog] = None,
                 matmul_impl: Optional[str] = None,
                 liveness: Optional[LivenessConfig] = None):
        assert mode in SHARD_MODES, mode
        self.pool = pool
        self.mode = mode
        self.hedging = hedging
        self.liveness = liveness or LivenessConfig()
        # kernels/limb_matmul/ops.field_matmul impl override for the shard
        # matmuls (None = auto). Simulated pools on CPU want "ref": the
        # interpreted-Pallas path auto picks for large shapes is
        # Python-level and GIL-bound, which would serialize the per-device
        # worker threads the simulation relies on; the jnp ref backend is
        # bit-identical and releases the GIL.
        self.matmul_impl = matmul_impl
        # shard wall times feed the watchdog; its P50 sets the hedge
        # deadline (deadline_factor × P50 after warmup)
        self.watchdog = watchdog or StepWatchdog(WatchdogConfig(
            deadline_factor=3.0, warmup_steps=4, window=64))
        self.report = ShardReport()         # current-infer counters
        self.totals = ShardReport()         # lifetime counters
        # optional runtime/profiling.FlightRecorder (the engine attaches
        # its own at register time): bad shard outcomes land in the
        # post-mortem ring even though the plane recovers them locally
        self.recorder = None
        self._lock = threading.Lock()

    @property
    def n_shards(self) -> int:
        return self.pool.size

    def begin_infer(self) -> None:
        """Reset the per-infer report (the executor calls this per trace)."""
        self.report = ShardReport()

    # -- internals ---------------------------------------------------------
    def _record(self, **deltas: int) -> None:
        with self._lock:
            for k, v in deltas.items():
                setattr(self.report, k, getattr(self.report, k) + v)
                setattr(self.totals, k, getattr(self.totals, k) + v)

    def _span_start(self, name: str, **attrs):
        """Open a child span of the ambient parent (the op's
        "shard.matmul" span — submission AND resolution both run on the
        batcher thread, so the contextvar parent is always right). None
        when no tracer is active."""
        tr = tracing.current_tracer()
        if tr is None:
            return None
        return tr.start_span(name, "shard", **attrs)

    def _span_end(self, span, **attrs) -> None:
        if span is None:
            return
        tr = tracing.current_tracer()
        if tr is not None:
            tr.end(span, **attrs)

    def _rec_event(self, outcome: str, slot: DeviceSlot) -> None:
        """Log a bad shard outcome to the attached flight recorder."""
        if self.recorder is not None:
            self.recorder.event("shard_" + outcome, device=slot.name)

    def _observe_latency(self, dt: float) -> None:
        with self._lock:
            self.watchdog.start_step(now=0.0)
            self.watchdog.end_step(now=dt)

    def _hedge_deadline(self) -> Optional[float]:
        with self._lock:
            return self.watchdog.deadline(floor=1e-4)

    def _dispatch_timeout(self) -> float:
        """Hard liveness deadline for one shard dispatch: same watchdog
        baseline as the hedge, larger factor + a floor (a hedge fires a
        duplicate; a timeout indicts the device)."""
        lv = self.liveness
        with self._lock:
            return self.watchdog.deadline(factor=lv.timeout_factor,
                                          floor=lv.timeout_floor_s,
                                          cold=lv.cold_timeout_s)

    def _backoff(self, task: _ShardTask, attempt: int) -> None:
        """Sleep before liveness re-dispatch attempt ``attempt`` of one
        shard: exponential with deterministic jitter in (op, shard,
        attempt) — a flake storm across shards de-synchronizes instead of
        stampeding the surviving devices."""
        lv = self.liveness
        u = random.Random(FT.stable_seed(task.op_index, task.index,
                                         attempt)).random()
        dt = min(lv.backoff_base_s * (lv.backoff_factor ** attempt),
                 lv.backoff_max_s) * (1.0 + lv.backoff_jitter * u)
        self._record(backoffs=1)
        time.sleep(dt)

    def _device_run(self, slot: DeviceSlot, task: _ShardTask,
                    w_q: jax.Array):
        """Runs ON the slot's worker thread: the untrusted device's half.

        Returns (y_field, wall_s). The slot's fault injector corrupts the
        result exactly where a byzantine accelerator would; the liveness
        injector crashes/parks/delays the dispatch exactly where a dead
        or braked device would; the latency model (sim_gflops /
        sim_delay_s) sleeps out the modeled compute time so hedging and
        the bench see realistic wall clocks."""
        t0 = time.perf_counter()
        if slot.liveness is not None:
            slot.liveness.perturb(op_index=task.op_index,
                                  cancel=slot.cancel)
        x = task.x
        if slot.jax_device is not None:
            # both operands go to the slot's device, and the product comes
            # back to the enclave's device, where it is verified and joined
            home = next(iter(x.devices()))
            x = jax.device_put(x, slot.jax_device)
            w_q = jax.device_put(w_q, slot.jax_device)
        y = self._matmul(x, w_q)
        if slot.fault is not None:
            y, _ = slot.fault.corrupt(y, op_index=task.op_index,
                                      key=task.fault_key,
                                      will_verify=jnp.bool_(True))
        y = jax.block_until_ready(y)
        if slot.jax_device is not None:
            y = jax.block_until_ready(jax.device_put(y, home))
        if slot.sim_gflops:
            flops = 2 * x.shape[0] * x.shape[1] * y.shape[1]
            time.sleep(flops / (slot.sim_gflops * 1e9))
        if slot.sim_delay_s:
            time.sleep(slot.sim_delay_s)
        return y, time.perf_counter() - t0

    def _matmul(self, x: jax.Array, w_q: jax.Array) -> jax.Array:
        if self.matmul_impl is None:
            return field_matmul(x, w_q)
        return field_matmul(x, w_q, impl=self.matmul_impl)

    @staticmethod
    def _shard_ok(y: jax.Array, task: _ShardTask) -> bool:
        return bool(IG.fold_check(y, task.x, task.s, task.ws))

    def _enclave_shard(self, task: _ShardTask, w_q: jax.Array) -> jax.Array:
        """Enclave computes this shard itself (last resort) — traced as its
        own child span so post-hoc analysis sees WHERE offload gave up."""
        self._record(enclave_shards=1)
        with tracing.maybe_span("shard.enclave", "shard",
                                shard=task.index, op_index=task.op_index):
            return field_matmul(task.x, w_q)

    def _resolve_shard(self, task: _ShardTask, w_q: jax.Array,
                       primary: DeviceSlot, fut,
                       spares: Sequence[DeviceSlot],
                       span=None) -> jax.Array:
        """One shard, submitted ``fut`` to verified finish: hedge onto the
        first spare past the straggler deadline, contain crashes, abandon
        dispatches past the hard liveness timeout, retry failures
        (integrity or liveness) down the spare list, enclave-compute as
        last resort. (All shards' primaries are submitted BEFORE any is
        resolved — ``matmul`` — so distinct devices genuinely overlap.)

        ``span``: the primary dispatch's open trace span (from the submit
        site); every re-dispatch/hedge opens its own, and each closes with
        an ``outcome`` attribute when its future resolves."""
        futures: Dict[object, Tuple[DeviceSlot, float, object]] = {
            fut: (primary, time.perf_counter(), span)}
        spares = list(spares)
        hedged = False
        attempt = 0                    # liveness re-dispatches of this shard
        hedge_deadline = self._hedge_deadline()

        def next_spare() -> Optional[DeviceSlot]:
            # re-check health at use time: the spares list was captured
            # before this op's earlier shards may have indicted one of them
            busy = {v[0] for v in futures.values()}
            return next((s for s in spares
                         if s.available and s not in busy), None)

        def submit_to(slot: DeviceSlot, why: str) -> None:
            futures[slot.submit(self._device_run, task, w_q)] = (
                slot, time.perf_counter(),
                self._span_start("shard.dispatch", shard=task.index,
                                 op_index=task.op_index, device=slot.name,
                                 attempt=why))

        def redispatch() -> bool:
            """Backoff, then re-submit this shard to the next spare."""
            nonlocal attempt
            retry = next_spare()
            if retry is None:
                return False
            spares.remove(retry)
            attempt += 1
            self._backoff(task, attempt)
            submit_to(retry, "retry")
            self._record(dispatches=1, retries=1)
            return True

        while futures:
            hard = self._dispatch_timeout()
            now = time.perf_counter()
            wait_t = min(max(v[1] + hard - now, 0.0)
                         for v in futures.values())
            if not hedged and hedge_deadline is not None:
                wait_t = min(wait_t, hedge_deadline)
            done, _ = wait(list(futures), timeout=wait_t,
                           return_when=FIRST_COMPLETED)
            if not done:
                now = time.perf_counter()
                expired = [f for f, v in futures.items()
                           if now - v[1] >= hard]
                if expired:
                    # hard liveness timeout: indict the device, cut its
                    # wedged queue loose so later probes never line up
                    # behind the hung dispatch, re-dispatch elsewhere
                    for f in expired:
                        slot, _, sp = futures.pop(f)
                        self._span_end(sp, outcome="timeout")
                        self._record(timeouts=1)
                        self._rec_event("timeout", slot)
                        self.pool.record_liveness_failure(slot)
                        slot.abandon()
                    if not futures and not redispatch():
                        return self._enclave_shard(task, w_q)
                    continue
                # straggler (still inside the hard deadline): hedge once
                spare = next_spare()
                if self.hedging and not hedged and spare is not None:
                    hedged = True
                    spares.remove(spare)
                    submit_to(spare, "hedge")
                    self._record(dispatches=1, hedges=1)
                hedge_deadline = None  # hard expiries drive the waits now
                continue
            fut = next(iter(done))
            slot, _, sp = futures.pop(fut)
            try:
                y, dt = fut.result()
            except Exception:  # noqa: BLE001 — crash containment (§12)
                # the dispatch raised (injected crash, driver error,
                # abandoned-queue cancellation): a liveness failure of the
                # DEVICE, contained here — it never reaches the batch
                self._span_end(sp, outcome="crash")
                self._record(crashes=1)
                self._rec_event("crash", slot)
                self.pool.record_liveness_failure(slot)
                if not futures and not redispatch():
                    return self._enclave_shard(task, w_q)
                continue
            self._observe_latency(dt)
            self._record(checks=1)
            if self._shard_ok(y, task):
                self._span_end(sp, outcome="verified", device_wall_s=dt)
                self.pool.record_success(slot, dt)
                # a hedge loser still teaches the EWMA its wall time
                for f, v in futures.items():
                    self._span_end(v[2], outcome="superseded")
                    f.add_done_callback(
                        lambda f_, s_=v[0]: self._late_latency(f_, s_))
                return y
            self._span_end(sp, outcome="verify_failed", device_wall_s=dt)
            self._record(failures=1)
            self._rec_event("verify_failed", slot)
            self.pool.record_failure(slot)
            if not futures:                    # re-dispatch THIS shard only
                retry = next_spare()
                if retry is None:
                    return self._enclave_shard(task, w_q)
                spares.remove(retry)
                submit_to(retry, "retry")
                self._record(dispatches=1, retries=1)
        raise AssertionError("unreachable: shard loop exited without result")

    def _late_latency(self, fut, slot: DeviceSlot) -> None:
        try:
            _, dt = fut.result()
        except Exception:  # noqa: BLE001 — a dead hedge loser is ignorable
            return
        self._observe_latency(dt)
        self.pool.record_latency(slot, dt)

    # -- public API --------------------------------------------------------
    def matmul(self, x_field: jax.Array, w_q: jax.Array, *,
               session_key: jax.Array, op_index: int, step: int = 0,
               k: int = 1,
               folds: Optional[Sequence[Tuple[jax.Array, jax.Array]]] = None,
               mode: Optional[str] = None,
               group: Optional[Sequence[int]] = None) -> jax.Array:
        """``(x_field @ w_q) mod p`` sharded across the pool.

        ``folds``: per-shard (s_j, ws_j) from the precompute ring (derived
        live — same streams — when absent). ``mode``/``group``: per-step
        ShardPolicy overrides (core/plan.py). Bit-identical to
        ``field_matmul(x_field, w_q)`` for any device behavior the checks
        and retries can recover from."""
        mode = mode or self.mode
        assert mode in SHARD_MODES, mode
        # one "shard.matmul" span per sharded op; every dispatch/retry/
        # hedge/enclave child parents to it (all created on this thread).
        # Shapes and counts only — the operands are blinded but redaction
        # would reject them anyway (core/tracing.py).
        with tracing.maybe_span("shard.matmul", "shard", op_index=op_index,
                                step=step, mode=mode,
                                n_shards=self.n_shards,
                                t=int(x_field.shape[0]),
                                d_in=int(x_field.shape[1]),
                                d_out=int(w_q.shape[1])):
            return self._sharded_matmul(x_field, w_q,
                                        session_key=session_key,
                                        op_index=op_index, step=step, k=k,
                                        folds=folds, mode=mode, group=group)

    def _sharded_matmul(self, x_field: jax.Array, w_q: jax.Array, *,
                        session_key: jax.Array, op_index: int, step: int,
                        k: int,
                        folds: Optional[Sequence[Tuple[jax.Array,
                                                       jax.Array]]],
                        mode: str,
                        group: Optional[Sequence[int]]) -> jax.Array:
        n = self.n_shards
        t, d_in = x_field.shape
        d_out = w_q.shape[1]
        self.pool.begin_dispatch()
        self._record(ops=1)

        if mode == "rows":
            spans = row_spans(t, n)
            operands = [x_field[lo:hi] for lo, hi in spans]
        else:
            operands = additive_shares(x_field, session_key, op_index,
                                       step, n)

        tasks: List[Optional[_ShardTask]] = []
        fault_root = B.stream_key(
            jax.random.fold_in(session_key, _SHARD_FAULT), op_index, step)
        for j, xj in enumerate(operands):
            if xj.shape[0] == 0:               # t < n: nothing to compute
                tasks.append(None)
                continue
            if folds is not None:
                s, ws = folds[j]
            else:
                s = IG.shard_fold_stream(session_key, op_index, step, j,
                                         d_out, k)
                ws = field_matmul(w_q, s)
            tasks.append(_ShardTask(j, op_index, xj, s, ws,
                                    jax.random.fold_in(fault_root, j)))

        healthy = self.pool.healthy(group)
        probe = self.pool.probe_candidate(group)
        bprobe = self.pool.breaker_candidate(group)
        probe_j = max((j for j, tk in enumerate(tasks) if tk is not None),
                      default=None)
        # the liveness probe rides the lowest shard so the two probe kinds
        # never collide; with a single shard the integrity probe wins and
        # the breaker probe waits for the next op
        bprobe_j = min((j for j, tk in enumerate(tasks) if tk is not None),
                       default=None)
        if probe is not None and bprobe_j == probe_j:
            bprobe = None
        results: List[Optional[jax.Array]] = [None] * n
        # submit EVERY shard's primary before resolving any — shards on
        # distinct devices overlap; resolution (verify/hedge/retry) then
        # consumes them in shard order
        pending: List[Tuple[int, _ShardTask, DeviceSlot, object,
                            List[DeviceSlot], object]] = []
        for j, task in enumerate(tasks):
            if task is None:
                results[j] = jnp.zeros((0, d_out), x_field.dtype)
                continue
            if probe is not None and j == probe_j:
                # the probation probe: one verified shard on the benched
                # device; a clean check restores it, a failed one re-benches
                # it and the shard retries on the healthy list as usual
                primary, spares = probe, list(healthy)
            elif bprobe is not None and j == bprobe_j:
                # the breaker probe: one shard on the half-open device; a
                # verified success closes the breaker (record_success), a
                # crash/timeout re-opens it with a doubled cooldown and the
                # shard retries on the healthy list / enclave as usual
                primary, spares = bprobe, list(healthy)
            elif healthy:
                if mode == "shares":
                    # a device may hold AT MOST ONE share of an op —
                    # wrapping around (or retrying/hedging a share onto a
                    # device that already holds another) would hand one
                    # device enough shares to reconstruct the full blinded
                    # tensor, the exact thing shares mode exists to prevent
                    primary = healthy[j] if j < len(healthy) else None
                else:
                    primary = healthy[j % len(healthy)]
                spares = [s for s in healthy if s is not primary]
            else:
                primary, spares = None, []
            if mode == "shares":
                spares = []        # one device per share, ever (DESIGN §11)
            if primary is None:
                # no device this shard may visit: the enclave computes it
                results[j] = self._enclave_shard(task, w_q)
                continue
            why = "primary"
            if primary is probe:
                self.pool.record_probe(primary)
                self._record(probes=1)
                why = "probe"
            elif primary is bprobe:
                self.pool.record_breaker_probe(primary)
                self._record(breaker_probes=1)
                why = "breaker_probe"
            span = self._span_start("shard.dispatch", shard=j,
                                    op_index=op_index, device=primary.name,
                                    attempt=why)
            fut = primary.submit(self._device_run, task, w_q)
            self._record(dispatches=1)
            pending.append((j, task, primary, fut, spares, span))
        for j, task, primary, fut, spares, span in pending:
            results[j] = self._resolve_shard(task, w_q, primary, fut,
                                             spares, span=span)

        if mode == "rows":
            return jnp.concatenate(results, axis=0)
        out = results[0]
        for y in results[1:]:
            if y.shape[0]:
                out = jnp.mod(out + y, P)
        return out

    def snapshot(self) -> Dict[str, object]:
        lv = self.liveness
        with self._lock:
            totals = dataclasses.asdict(self.totals)
            # the plane's straggler/liveness brain, exported (DESIGN.md
            # §13): the hedge and abandon deadlines in force RIGHT NOW,
            # so a post-hoc chaos drill can explain every hedge/timeout
            watchdog = {
                "p50_s": self.watchdog.p50,
                "samples": len(self.watchdog.history),
                "flagged_steps": self.watchdog.flagged_steps,
                "hedge_deadline_s": self.watchdog.deadline(floor=1e-4),
                "dispatch_timeout_s": self.watchdog.deadline(
                    factor=lv.timeout_factor, floor=lv.timeout_floor_s,
                    cold=lv.cold_timeout_s),
            }
        return {"mode": self.mode, "hedging": self.hedging,
                "totals": totals, "watchdog": watchdog,
                "pool": self.pool.snapshot()}
