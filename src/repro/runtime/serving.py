"""Private-inference serving loop (the paper's deployment shape).

Flow per Fig. 3a: client attests the enclave (core/attestation), seals its
input under the session key (core/sealing), the enclave unseals inside the
trust boundary, the OrigamiExecutor runs tier-1 blinded + tier-2 open, and
the result is sealed back to the client. Requests are micro-batched with
padding; the watchdog (runtime/straggler) monitors per-batch latency.

``PrivateInferenceServer`` is the synchronous single-model front end.
``serve_batch`` is the one-enclave-dispatch primitive (unseal -> filter
failed MACs -> pad -> blinded infer -> seal); ``serve`` is now a thin
compat wrapper over the async ``ServingEngine`` (runtime/engine.py), which
adds continuous micro-batching, deadlines, admission control and an N-deep
blinding-session pool (runtime/sessions.py) on top of the same primitive.

Nonce discipline: requests seal under the 64-bit rid split
``[lo, hi]``; responses under ``[lo, hi, DIRECTION_RESPONSE]`` — same
split, third word tags the direction, so no (key, nonce) pair is ever
reused between the two directions or between rids differing only in high
bits (the seed truncated the response nonce to 32 rid bits).
"""
from __future__ import annotations

import dataclasses
import os
import time
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import tracing
from repro.core.attestation import Quote, measure_enclave, verify_quote
from repro.core.origami import OrigamiExecutor
from repro.core.sealing import SealedBox, open_response, seal, unseal
from repro.runtime.aot import bucket_for
from repro.runtime.straggler import StepWatchdog

# third nonce word for enclave->client traffic (requests use 2-word nonces;
# sealing._keystream folds nonce words sequentially, so the domains differ)
DIRECTION_RESPONSE = 0xEE

# fold_in tag deriving a fresh blinding session for an integrity retry /
# enclave recompute when the caller supplied a fixed key instead of a pool
# (a re-run must never reuse the failed attempt's one-time pads)
_RETRY_DOMAIN = 0x0E7B1


def request_nonce(rid: int) -> jax.Array:
    return jnp.asarray([rid & 0xFFFFFFFF, (rid >> 32) & 0xFFFFFFFF],
                       jnp.uint32)


def response_nonce(rid: int) -> jax.Array:
    """Full 64-bit rid split + direction tag (not the seed's 32-bit
    truncation, which reused a (key, nonce) pair across rids that differed
    only in their high 32 bits)."""
    return jnp.asarray([rid & 0xFFFFFFFF, (rid >> 32) & 0xFFFFFFFF,
                        DIRECTION_RESPONSE], jnp.uint32)


@dataclasses.dataclass
class Request:
    rid: int
    box: SealedBox
    shape: Tuple[int, ...]
    session_key: np.ndarray          # client's symmetric key material


@dataclasses.dataclass
class Response:
    rid: int
    box: Optional[SealedBox]
    ok: bool
    latency_s: float
    # integrity mark (DESIGN.md §9): True when a Freivalds check failed on
    # this request's batch and the logits were recovered (device retry or
    # enclave recompute) before sealing — served correctly, but the client
    # / operator can see the device misbehaved.
    flagged: bool = False
    # machine-readable failure cause when ok=False (DESIGN.md §12):
    # "mac_failed" (request never reached the executor),
    # "deadline_exceeded" (expired at batch formation or dispatch),
    # "shutdown" (engine closed with this request still queued),
    # "rejected" (admission control: queue full, unknown model, or a
    # duplicate in-flight rid). None on every ok=True response.
    error: Optional[str] = None


@dataclasses.dataclass
class BatchIntegrity:
    """Verification outcome of one sealed-batch dispatch (all requests in
    a batch share the blinded trace, so detection and recovery are
    batch-granular)."""
    checks: int = 0              # Freivalds checks that ran (all attempts)
    failures: int = 0            # checks that mismatched
    corrupted: int = 0           # injector ground truth (tests/benchmarks)
    retried: bool = False        # one fresh-session device retry happened
    recomputed: bool = False     # enclave recompute produced the response
    trusted: bool = False        # dispatched straight to the enclave
                                 # (quarantined backend — no checks to run)
    # multi-device plane counters (parallel/offload_sharding.py): shard
    # failures are detected AND recovered inside the op (single-shard
    # retry on another device), so they never trigger the batch-level
    # retry/recompute path — but they still flag the response.
    shard_checks: int = 0        # shard-local Freivalds checks run
    shard_failures: int = 0      # shard checks that mismatched
    shard_retries: int = 0       # single-shard re-dispatches
    shard_hedges: int = 0        # straggler duplicates launched
    shard_enclave: int = 0       # shards the enclave computed itself
    # liveness ladder (DESIGN.md §12): contained inside the op like shard
    # integrity failures — recovered before the batch ever sees them
    shard_crashes: int = 0       # dispatches that raised (contained)
    shard_timeouts: int = 0      # dispatches abandoned past the deadline

    @property
    def flagged(self) -> bool:
        return self.failures > 0 or self.shard_failures > 0


@dataclasses.dataclass
class IntegrityTotals:
    """Running sums over many dispatches (per-batch bools become counts —
    a sticky ``or`` would report 'recomputed=True' whether 1 or 50 batches
    needed the enclave)."""
    checks: int = 0
    failures: int = 0
    corrupted: int = 0
    retries: int = 0
    recomputes: int = 0
    trusted_batches: int = 0
    shard_checks: int = 0
    shard_failures: int = 0
    shard_retries: int = 0
    shard_hedges: int = 0
    shard_enclave: int = 0
    shard_crashes: int = 0
    shard_timeouts: int = 0

    def add(self, integ: BatchIntegrity) -> None:
        self.checks += integ.checks
        self.failures += integ.failures
        self.corrupted += integ.corrupted
        self.retries += integ.retried
        self.recomputes += integ.recomputed
        self.trusted_batches += integ.trusted
        self.shard_checks += integ.shard_checks
        self.shard_failures += integ.shard_failures
        self.shard_retries += integ.shard_retries
        self.shard_hedges += integ.shard_hedges
        self.shard_enclave += integ.shard_enclave
        self.shard_crashes += integ.shard_crashes
        self.shard_timeouts += integ.shard_timeouts


def _fresh_session(session_key, used: jax.Array) -> jax.Array:
    """A never-used blinding session for a device retry: next pool key
    when the caller gave us a pool, else a tagged derivation of the used
    key (one-time pads must not repeat across attempts)."""
    if callable(session_key):
        return session_key()
    return jax.random.fold_in(used, _RETRY_DOMAIN)


def _trusted_key() -> jax.Array:
    """The enclave-recompute trace draws no blinding streams, no verify
    keys and no fault keys — its session key is semantically unused, so a
    constant keeps trusted dispatches from burning pool sessions."""
    return jax.random.PRNGKey(0)


@dataclasses.dataclass
class PreparedBatch:
    """Product of the enclave stage of one sealed-batch dispatch: requests
    unsealed, failed MACs filtered, survivors stacked and zero-padded to a
    shape bucket. Everything after this (infer/verify/recovery/seal) is the
    device stage — the two-stage serving pipeline (runtime/engine.py)
    overlaps batch N+1's prepare with batch N's completion across threads,
    handing exactly this object between them."""
    requests: List[Request]
    boxes: List[Optional[SealedBox]]     # positional; None = MAC failed
    valid_idx: List[int]
    x: Optional[jax.Array]               # bucket-padded input, None if empty
    pad: int                             # zero rows added (bucket - n_valid)
    bucket: int                          # padded batch dim (0 if empty)
    integ: BatchIntegrity

    @property
    def n_valid(self) -> int:
        return len(self.valid_idx)


def prepare_sealed_batch(requests: List[Request], *, max_batch: int,
                         input_dtype: Optional[str] = None) -> PreparedBatch:
    """Enclave stage: unseal -> filter failed MACs -> bucket-pad.

    Padding goes to the smallest power-of-two shape bucket that holds the
    survivors (``aot.bucket_for``), not straight to ``max_batch``: a lone
    request in a quiet period pads to 1 row of work, not 8. The bucket is
    a pure function of the valid count, so any two paths fed the same
    request list pick the same bucket — and hence the same compiled
    executable, which is what keeps the engine bit-identical to the legacy
    oracle (XLA may legally pick different float kernels at different
    batch shapes). Zero pad rows are exact no-ops for the blinded trace:
    they never raise the activation absmax, so the quantization scale —
    and therefore every data row's logits — is untouched.
    """
    valid_idx: List[int] = []
    inputs: List[np.ndarray] = []
    with tracing.maybe_span("unseal", "crypto",
                            n_requests=len(requests)) as usp:
        for i, r in enumerate(requests):
            pt, ok = unseal(jnp.asarray(r.session_key, jnp.uint32), r.box,
                            r.shape)
            if ok:
                valid_idx.append(i)
                inputs.append(np.asarray(pt))
        tracing.annotate(usp, n_valid=len(inputs))
    boxes: List[Optional[SealedBox]] = [None] * len(requests)
    integ = BatchIntegrity()
    if not inputs:
        return PreparedBatch(requests, boxes, valid_idx, None, 0, 0, integ)
    bucket = bucket_for(len(inputs), max_batch)
    pad = bucket - len(inputs)
    x = jnp.asarray(np.stack(inputs + [np.zeros_like(inputs[0])] * pad))
    if input_dtype is not None:          # LM tokens ride as f32 payloads
        x = x.astype(input_dtype)
    return PreparedBatch(requests, boxes, valid_idx, x, pad, bucket, integ)


def execute_sealed_batch(executor: OrigamiExecutor, requests: List[Request],
                         *, input_key: str, max_batch: int,
                         session_key, input_dtype: Optional[str] = None,
                         trusted: bool = False, retry_device: bool = True
                         ) -> Tuple[List[Optional[SealedBox]], int, int,
                                    BatchIntegrity]:
    """The one sealed-batch primitive both serving paths share:
    unseal -> filter failed MACs -> bucket-pad -> blinded infer
    (Freivalds-verified per the executor's policy) -> recover on failure ->
    seal responses. Composition of ``prepare_sealed_batch`` (enclave
    stage) and ``complete_prepared_batch`` (device stage) — the pipelined
    engine calls the two halves on different threads, so this composition
    IS the single-threaded legacy oracle it is cross-checked against.

    Returns ``(boxes, n_valid, pad, integrity)`` with ``boxes`` positional —
    ``boxes[i] is None`` iff request i failed its MAC (it never reached
    the executor: no inference slot, no blinding, no telemetry skew).
    ``session_key`` may be a zero-arg callable (e.g. ``SessionPool.
    acquire``), only invoked once at least one valid request will reach
    the executor — an all-invalid batch must not burn a blinding session.

    Integrity flow (DESIGN.md §9): a failed check discards the device's
    answer; ``retry_device`` grants one re-offload under a fresh blinding
    session (a transient fault clears, a persistent adversary fails
    again), after which the enclave recomputes the batch itself —
    ``trusted=True`` (engine quarantine) skips the device entirely. The
    blinded result is session-independent, so every recovery path is
    bit-identical to an honest device's response.
    """
    prep = prepare_sealed_batch(requests, max_batch=max_batch,
                                input_dtype=input_dtype)
    if prep.x is None:
        return prep.boxes, 0, 0, prep.integ
    return complete_prepared_batch(executor, prep, input_key=input_key,
                                   session_key=session_key, trusted=trusted,
                                   retry_device=retry_device)


def complete_prepared_batch(executor: OrigamiExecutor, prep: PreparedBatch,
                            *, input_key: str, session_key,
                            trusted: bool = False, retry_device: bool = True
                            ) -> Tuple[List[Optional[SealedBox]], int, int,
                                       BatchIntegrity]:
    """Device stage: blinded infer -> verify -> §9 recovery ladder -> seal.

    ``prep.x`` must be non-None (the caller short-circuits empty batches).
    A batch that fails its Freivalds check drains through the full
    detect -> retry -> recompute ladder *inside this stage*, on whichever
    thread runs it — the pipeline never reorders or splits a batch's
    recovery."""
    requests, boxes, integ = prep.requests, prep.boxes, prep.integ
    valid_idx, pad = prep.valid_idx, prep.pad
    n_valid = prep.n_valid
    batch = {input_key: prep.x}
    if trusted:
        # the trusted trace neither blinds nor verifies, so it consumes no
        # session material — do NOT pop a pool key (its prefetched factor
        # set would be generated and never taken)
        integ.trusted = True
        with tracing.maybe_span("infer", "infer", attempt="trusted",
                                trusted=True):
            result = executor.infer(batch, session_key=_trusted_key(),
                                    trusted=True)
    else:
        def absorb_shards(res) -> None:
            if res.sharding is None:
                return
            integ.shard_checks += res.sharding.checks
            integ.shard_failures += res.sharding.failures
            integ.shard_retries += res.sharding.retries
            integ.shard_hedges += res.sharding.hedges
            integ.shard_enclave += res.sharding.enclave_shards
            integ.shard_crashes += res.sharding.crashes
            integ.shard_timeouts += res.sharding.timeouts

        with tracing.maybe_span("session.acquire", "session",
                                pooled=callable(session_key)):
            sk = session_key() if callable(session_key) else session_key
        with tracing.maybe_span("infer", "infer", attempt="blinded") as isp:
            result = executor.infer(batch, session_key=sk)
            tracing.annotate(isp, checks=result.integrity.n_checked,
                             failures=result.integrity.n_failed)
        integ.checks = result.integrity.n_checked
        integ.failures = result.integrity.n_failed
        integ.corrupted = result.integrity.n_corrupted
        absorb_shards(result)
        if not result.integrity.ok and retry_device:
            with tracing.maybe_span("session.acquire", "session",
                                    pooled=callable(session_key),
                                    retry=True):
                sk = _fresh_session(session_key, sk)
            with tracing.maybe_span("infer", "infer",
                                    attempt="retry") as isp:
                result = executor.infer(batch, session_key=sk)
                tracing.annotate(isp, checks=result.integrity.n_checked,
                                 failures=result.integrity.n_failed)
            integ.retried = True
            integ.checks += result.integrity.n_checked
            integ.failures += result.integrity.n_failed
            integ.corrupted += result.integrity.n_corrupted
            absorb_shards(result)
        if not result.integrity.ok:
            with tracing.maybe_span("infer", "infer", attempt="recompute",
                                    trusted=True):
                result = executor.infer(batch, session_key=_trusted_key(),
                                        trusted=True)
            integ.recomputed = True
        # summary of the batch's verification outcome, one span so the
        # tree reads queue -> batch -> ... -> verify -> seal even though
        # the checks themselves ran inside the infer attempts
        with tracing.maybe_span("verify", "verify", checks=integ.checks,
                                failures=integ.failures,
                                shard_checks=integ.shard_checks,
                                shard_failures=integ.shard_failures,
                                retried=integ.retried,
                                recomputed=integ.recomputed):
            pass
    with tracing.maybe_span("seal", "crypto", n_responses=len(valid_idx),
                            pad=pad):
        logits = np.asarray(result.logits, np.float32)[:n_valid]
        for row, i in enumerate(valid_idx):
            r = requests[i]
            boxes[i] = seal(jnp.asarray(r.session_key, jnp.uint32),
                            jnp.asarray(logits[row]), response_nonce(r.rid))
    return boxes, n_valid, pad, integ


class PrivateInferenceServer:
    """Batched Origami serving over a model (CNN or LM single-shot)."""

    def __init__(self, cfg: ModelConfig, params, *, mode: str = "origami",
                 max_batch: int = 8, input_key: str = "images",
                 impl: str = "fused", precompute: bool = True,
                 integrity=None, fault=None, plan=None):
        """``plan``: an explicit core/plan.PlacementPlan; when omitted the
        legacy ``mode`` kwarg compiles one (OrigamiExecutor compat)."""
        self.cfg = cfg
        self.executor = OrigamiExecutor(cfg, params, mode=mode, impl=impl,
                                        precompute=precompute,
                                        integrity=integrity, fault=fault,
                                        plan=plan)
        self.quote = measure_enclave(cfg, params, self.executor.partition,
                                     plan_digest=self.executor.plan.digest)
        self.max_batch = max_batch
        self.input_key = input_key
        self.watchdog = StepWatchdog()
        self.processed = 0
        self.batches = 0
        self.integrity_totals = IntegrityTotals()  # running serve_batch sums
        self._engine = None              # lazy ServingEngine (serve())
        # server-side root for per-batch blinding sessions (distinct from the
        # clients' sealing keys): batch k runs under fold_in(root, k). MUST
        # be fresh entropy per instance — a fixed or colliding root would
        # reuse one-time pads across server restarts/replicas, letting the
        # device subtract two blinded tensors and cancel r. 64 entropy bits
        # via two 32-bit words (PRNGKey seeds are limited to C-long range).
        w0, w1 = np.frombuffer(os.urandom(8), np.uint32)
        self._blind_root = jax.random.fold_in(jax.random.PRNGKey(int(w0)),
                                              int(w1))

    def _blind_session(self, batch_idx: int) -> jax.Array:
        return jax.random.fold_in(self._blind_root, batch_idx)

    # -- client side helpers ---------------------------------------------
    def attest(self) -> Quote:
        return self.quote

    @staticmethod
    def client_seal(key: np.ndarray, x: np.ndarray, rid: int) -> SealedBox:
        return seal(jnp.asarray(key, jnp.uint32), jnp.asarray(x),
                    request_nonce(rid))

    @staticmethod
    def client_open(key: np.ndarray, box: SealedBox,
                    shape: Tuple[int, ...]) -> np.ndarray:
        pt, ok = open_response(jnp.asarray(key, jnp.uint32), box, shape)
        assert bool(ok), "response MAC failed"
        return np.asarray(pt)

    # -- server side -------------------------------------------------------
    def serve_batch(self, requests: List[Request]) -> List[Response]:
        """One enclave dispatch. Callers own batching: more than
        ``max_batch`` requests is an error (the seed silently dropped the
        tail) — use ``serve`` for arbitrary request lists."""
        if len(requests) > self.max_batch:
            raise ValueError(
                f"serve_batch got {len(requests)} requests for max_batch="
                f"{self.max_batch}; use serve() to micro-batch")
        self.watchdog.start_step()
        t0 = time.monotonic()
        boxes, n_valid, _, integ = execute_sealed_batch(
            self.executor, requests, input_key=self.input_key,
            max_batch=self.max_batch,
            session_key=self._blind_session(self.batches))
        self.integrity_totals.add(integ)
        if n_valid:
            self.batches += 1
            # double-buffer: enqueue the NEXT session's unblinding factors
            # now, so their field matmuls overlap this batch's device
            # compute (the engine's SessionPool deepens this to N)
            self.executor.prepare_session(self._blind_session(self.batches))
            self.processed += n_valid
        self.watchdog.end_step()
        dt = time.monotonic() - t0
        # positional assembly (not keyed by rid — rids may repeat)
        return [Response(r.rid, box, box is not None, dt,
                         flagged=integ.flagged and box is not None,
                         error=None if box is not None else "mac_failed")
                for r, box in zip(requests, boxes)]

    def serve(self, requests: List[Request]) -> List[Response]:
        """Compat wrapper: drives the async ServingEngine and returns
        responses in request order (the engine completes out of order).

        Legacy contract: every request gets a real answer. The engine
        rejects a rid that is already in flight, so duplicate rids are
        submitted in waves — each wave waits for the previous occurrence
        of its rid to finish.
        """
        responses: List[Optional[Response]] = [None] * len(requests)
        waves: List[List[int]] = []
        depth: dict = {}
        for i, r in enumerate(requests):
            d = depth.get(r.rid, 0)
            depth[r.rid] = d + 1
            while len(waves) <= d:
                waves.append([])
            waves[d].append(i)
        for wave in waves:
            futures = [(i, self.engine.submit("default", requests[i]))
                       for i in wave]
            # the list is complete — don't let a partial tail batch idle
            # out the max_wait timer
            self.engine.flush()
            for i, f in futures:
                responses[i] = f.result(timeout=300.0)
        return responses

    @property
    def engine(self):
        """Lazily-built single-model ServingEngine sharing this server's
        executor (so serve() and serve_batch() hit the same caches).

        ``max_queue`` is effectively unbounded: serve() is synchronous, so
        admission control would silently shed the tail of a long request
        list the legacy loop used to serve."""
        if self._engine is None:
            from repro.runtime.engine import EngineConfig, ServingEngine
            self._engine = ServingEngine(EngineConfig(
                max_batch=self.max_batch, max_wait_ms=25.0,
                max_queue=1_000_000_000))
            self._engine.register_executor("default", self.executor,
                                           input_key=self.input_key)
        return self._engine

    def close(self) -> None:
        """Stop the compat engine's batcher + session-pool threads (they
        are daemons, but long-lived processes creating many servers should
        release them and their prefetched factor sets deterministically)."""
        if self._engine is not None:
            self._engine.close()
            self._engine = None
