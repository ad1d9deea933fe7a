"""Origami executor: plan-driven trust-partitioned inference (the paper).

The executor interprets a ``PlacementPlan`` (core/plan.py): an explicit
per-layer placement IR — ``open`` | ``enclave`` | ``blinded`` plus optional
per-step Freivalds policies — compiled once and walked by ONE ``_traced``
for every model family (the per-family layer iterators live in
models/vgg.py / models/model.py). The five legacy mode strings

    "open"         everything on the untrusted device, no privacy
    "enclave"      everything inside the enclave (paper baseline 2)
    "split"        tier-1 in the enclave, tier-2 open (Split/x)
    "slalom"       blinded offload for EVERY layer (Slalom/Privacy)
    "origami"      blinded offload for tier-1 only, tier-2 open (the paper)

remain as thin compatibility constructors over ``plan.compile_mode`` —
there is no mode-string branching in the executor itself, and plans the
mode strings cannot express (mixed enclave/blinded tier-1, verified-open
tier-2 offload) execute through the same interpreter (DESIGN.md §10).

All plans compute the *same function* (up to tier-1 quantization error on
offloaded steps) — tests assert allclose against the open reference. Plans
differ in where work lands, which the trace-time telemetry records and
core/trust.py prices with the paper-calibrated cost model.
"""
from __future__ import annotations

import functools
from contextlib import ExitStack, nullcontext
from dataclasses import dataclass, field as dfield
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import integrity as IG
from repro.core import plan as PL
from repro.core import slalom as SL
from repro.core import tracing
from repro.core.blinding import BlindingSpec
from repro.core.precompute import BlindedLayerCache
from repro.models import layers as L
from repro.models import model as M
from repro.models import vgg as V
# repro.runtime is a namespace package and aot.py imports only jax, so
# this does not create a core <-> runtime import cycle
from repro.runtime import aot as AOT

MODES = PL.LEGACY_MODES

# XLA may keep a bfloat16 intermediate in float32 where a fusion allows it,
# so rounding would depend on the program around an op: the private and
# trusted traces of a bf16 model then part by an ulp. Every AOT executable
# rounds as the trace is written.
COMPILER_OPTIONS = {"xla_allow_excess_precision": False}


@dataclass
class OrigamiResult:
    logits: jax.Array
    boundary: Optional[jax.Array]       # what the adversary observes
    telemetry: SL.Telemetry
    integrity: IG.IntegrityReport = dfield(
        default_factory=IG.IntegrityReport.empty)
    trusted: bool = False               # enclave-recompute trace (no device)
    sharding: Optional[Any] = None      # offload_sharding.ShardReport


class OrigamiExecutor:
    """Plan-interpreting private inference over any repro model."""

    def __init__(self, cfg: ModelConfig, params, mode: str = "origami",
                 partition: Optional[int] = None,
                 spec: Optional[BlindingSpec] = None,
                 impl: str = "fused", precompute: bool = False,
                 integrity: Optional[IG.IntegrityPolicy] = None,
                 fault: Optional[Any] = None,
                 plan: Optional[PL.PlacementPlan] = None,
                 devices: Optional[Any] = None, shard: str = "rows",
                 hedging: bool = True, liveness: Optional[Any] = None):
        """``plan``: an explicit PlacementPlan; when omitted, the legacy
        ``mode``/``partition`` kwargs compile one (``plan.compile_mode``).
        ``integrity``: Freivalds verification policy inherited by blinded
        steps without their own (core/integrity.py; default off).
        ``fault``: a runtime/faults.DishonestDevice injected under the
        device matmul (single-device path; a pool carries per-slot
        injectors instead). ``devices``: a runtime/devices.DevicePool —
        attaches a sharded multi-device offload plane
        (parallel/offload_sharding.py) with default shard ``shard``
        ("rows" | "shares"), straggler ``hedging`` and a
        parallel/offload_sharding.LivenessConfig ``liveness`` (timeout /
        backoff / breaker knobs, defaults when None); the plane's
        host-side retry/health control flow makes the executor run its
        trace eagerly (bit-identical to the jitted trace). All are static
        — pick them at construction."""
        assert impl in ("fused", "unfused"), impl
        if plan is None:
            plan = PL.compile_mode(cfg, mode, partition)
        assert plan.n_layers == PL.num_blocks(cfg), \
            (plan.n_layers, PL.num_blocks(cfg))
        self.cfg = cfg
        self.params = params
        self.plan = plan
        self.mode = plan.mode_label          # compat: legacy name or spec
        self.partition = plan.boundary       # compat: revealed boundary
        self.spec = spec or BlindingSpec()
        self.impl = impl
        self.precompute = precompute
        self.integrity = integrity or IG.IntegrityPolicy.off()
        self.fault = fault
        self.plane = None
        self._plane_live = False
        if devices is not None:
            from repro.parallel.offload_sharding import OffloadPlane
            self.plane = OffloadPlane(devices, mode=shard, hedging=hedging,
                                      liveness=liveness)
            # the plane only ever fires on per-op-addressable offloaded
            # steps (scanned families and offload-free plans have none) —
            # keep jit for executors whose pool can never shard anything,
            # instead of paying op-by-op eager dispatch for zero benefit
            self._plane_live = (PL.linear_layers(cfg) is not None
                                and plan.has_offload)
        self.cache: Optional[BlindedLayerCache] = None
        self._caches: Dict[Any, BlindedLayerCache] = {}  # (digest, shape)
        self._cache_key = None
        self._program = PL.program_for(cfg)
        # per-trace telemetry (each trace gets its OWN recorder; the shared
        # object the seed used let the trusted-recovery trace corrupt the
        # offload counters). ``telemetry`` is the last-trace snapshot.
        self._tele_last = SL.Telemetry()
        self._tele_blinded = SL.Telemetry()
        self._tele_trusted = SL.Telemetry()
        # AOT serving path: executables compiled explicitly (lower+compile)
        # through a CompileCache (runtime/aot.py) instead of first-call jit.
        # Nothing is donated: no output has the shape of a session factor,
        # so XLA could alias none of them (on the TPU it only warned), and
        # the factors carry the cache's shared weight planes, which must
        # outlive every call. The §9 ladder also re-feeds the same batch
        # to the retry and enclave-recompute executables. Every traced
        # function takes the weights as its first argument (``_run``).
        self._aot_jit = jax.jit(self._traced)
        # the recovery path: same math with the field matmuls run inside
        # the enclave (no device, no blinding, no injector) — bit-identical
        # logits, used after a failed Freivalds check or under quarantine
        self._aot_jit_trusted = jax.jit(
            functools.partial(self._traced, trusted=True))
        self._aot: AOT.CompileCache = AOT.CompileCache(None)  # memo-only
        self._executables: Dict[Any, Any] = {}   # sig -> compiled (COW)
        # first-call signatures already inferred: the first (trace-kind,
        # plan, shapes) call pays jax.jit tracing + compilation, and the
        # profiler (runtime/profiling.py) needs that cold call *named* —
        # its infer span is stamped first_call=True
        self._seen_sigs: set = set()
        # decode plane (attach_decode_plan): scan segments + token-slot
        # factor caches, DESIGN.md §16
        self.dplan: Optional[PL.DecodePlan] = None
        self._decode_caches: Dict[int, BlindedLayerCache] = {}
        self._jit_decode = None
        self._jit_prefill = None

    # -- telemetry snapshots -------------------------------------------------
    @property
    def telemetry(self) -> SL.Telemetry:
        """Snapshot of the most recent trace (blinded or trusted)."""
        return self._tele_last

    @property
    def telemetry_blinded(self) -> SL.Telemetry:
        """Last untrusted (offload) trace — unpolluted by recovery traces."""
        return self._tele_blinded

    @property
    def telemetry_trusted(self) -> SL.Telemetry:
        """Last enclave-recompute trace."""
        return self._tele_trusted

    # -- layer count helpers -------------------------------------------------
    @property
    def num_blocks(self) -> int:
        return self.plan.n_layers

    # -- traced computation --------------------------------------------------
    def _traced(self, params, batch, session_key, factors=None,
                trusted=False):
        tele = SL.Telemetry()
        ctx = SL.SlalomContext(
            session_key, self.spec, telemetry=tele,
            impl=self.impl, factors=factors,
            integrity=IG.IntegrityPolicy.off(),  # set per plan segment
            fault=None if trusted else self.fault, trusted=trusted,
            plane=self.plane if self._plane_live and not trusted else None)
        logits, boundary = self._run(params, batch, ctx)
        if ctx.integrity_log:
            rep = tuple(jnp.stack([entry[i] for entry in ctx.integrity_log])
                        for i in range(3))
        else:
            z = jnp.zeros((0,), jnp.bool_)
            rep = (z, z, z)
        # runs at trace time: expose this trace's counters without letting
        # one trace kind pollute the other's
        if trusted:
            self._tele_trusted = tele
        else:
            self._tele_blinded = tele
        return logits, boundary, rep

    def _run(self, params, batch, ctx):
        """Walk the plan segments — the ONE interpreter for all families
        and all placements (no mode strings, no family forks).

        ``params`` (a jit argument under the AOT path) feeds the open
        segments, the prologue and the epilogue, so an executable does not
        embed those weights as constants: compiling with VGG-16's 0.55 GB
        embedded took 39.4 s on a TPU v5e, 4.9 s with it as an argument.
        Offloaded segments read ``self.params``, concrete at trace time:
        each blinded op then quantizes its weight exactly as the precompute
        cache does (core/blinding.py:quantize_weight), and stays
        individually addressable."""
        prog, plan = self._program, self.plan
        x, memory = prog.prologue(params, batch)
        # span per plan segment — EAGER traces only (the pooled plane path
        # and recovery paths): under jit the walk runs once at trace time,
        # so a span would clock compilation, not the step
        eager = not isinstance(x, jax.core.Tracer)
        boundary = x if plan.boundary == 0 else None
        for seg in plan.segments:
            with (tracing.maybe_span("plan.segment", "step", lo=seg.lo,
                                     hi=seg.hi, regime=seg.regime)
                  if eager else nullcontext()):
                if seg.regime == "plain":
                    x = prog.segment(params, x, seg.lo, seg.hi, memory)
                else:
                    policy = (seg.policy if seg.policy is not None
                              else self.integrity)
                    with ExitStack() as stack:
                        stack.enter_context(ctx.segment_overrides(
                            policy, unblinded=(seg.regime == "verified"),
                            shard=seg.shard))
                        stack.enter_context(L.dense_impl(
                            functools.partial(SL.blinded_dense, ctx)))
                        if prog.blind_convs:
                            stack.enter_context(L.conv_impl(
                                functools.partial(SL.blinded_conv2d, ctx)))
                        x = prog.segment(self.params, x, seg.lo, seg.hi,
                                         memory)
            if seg.hi == plan.boundary:
                boundary = x
        return prog.epilogue(params, x, batch, memory), boundary

    # -- decode plans: scan segments + token slots (DESIGN.md §16) -----------
    def attach_decode_plan(self, dplan: Optional[PL.DecodePlan] = None, *,
                           max_steps: int = 256) -> PL.DecodePlan:
        """Adopt a DecodePlan (core/plan.py:make_decode_plan) and stand up
        the decode interpreter: a jitted prompt pass over the BASE plan's
        segments and ONE jitted token step over the scan segments. Raises
        plan.ScanExclusion for families outside plan.DECODE_FAMILIES —
        the typed form of the former "scanned families fall back" branch.

        When ``dplan`` is omitted one is compiled from this executor's own
        plan, inheriting the executor's Freivalds policy as the per-step
        policy of every offloaded scan segment."""
        if dplan is None:
            dplan = PL.make_decode_plan(
                self.cfg, self.plan, max_steps=max_steps,
                integrity=(self.integrity if self.integrity.enabled
                           else None))
        assert dplan.base.digest == self.plan.digest, \
            "decode plan extends a different base plan"
        self.dplan = dplan
        self._jit_decode = jax.jit(self._traced_decode,
                                   static_argnames=("trusted",))
        self._jit_prefill = jax.jit(self._traced_prefill,
                                    static_argnames=("trusted", "max_seq"))
        return dplan

    def decode_cache(self, batch_size: int) -> Optional[BlindedLayerCache]:
        """Quantize-once weight material + per-(session, token, layer)
        factor store for the decode walk — one BlindedLayerCache per batch
        size, memoized. The TokenSlotRing (runtime/sessions.py) streams
        ``session_factors(key, step=token)`` out of it; the ``step`` slot
        of the factor keying IS the token index, so every (session, token,
        layer) triple draws a distinct pad (DESIGN.md §16). Returns None
        when the decode plan has no offloaded scan segments."""
        assert self.dplan is not None, "attach_decode_plan first"
        if not self.dplan.has_offload:
            return None
        cache = self._decode_caches.get(batch_size)
        if cache is None:
            cache = BlindedLayerCache.from_records(
                self._decode_records(batch_size), self.spec,
                integrity=self.integrity)
            # copy-on-write rebind: read by the ring's refill thread
            self._decode_caches = {**self._decode_caches,
                                   batch_size: cache}
        return cache

    def _decode_records(self, batch_size: int):
        """Static per-op descriptors for the decode walk, in trace order —
        captured by running one EAGER token step with a recording dense
        impl (weights are concrete here, unlike inside the jitted decode
        trace). Only offloaded scan segments record; plain segments run
        the scanned fast path and touch no factor material."""
        cfg, params = self.cfg, self.params
        records = []

        def capture(p, xx):
            w = p["w"]
            t = 1
            for s_ in xx.shape[:-1]:
                t *= s_
            records.append({"kind": "dense", "w": w, "t": int(t),
                            "d_in": int(w.shape[0]),
                            "d_out": int(w.shape[1])})
            y = xx @ w.astype(xx.dtype)
            if "b" in p:
                y = y + p["b"].astype(xx.dtype)
            return y

        caches = M.init_caches(cfg, batch_size, 8)
        token = jnp.zeros((batch_size, 1), jnp.int32)
        x = M.embed_tokens_at(params, token, jnp.int32(0), cfg)
        pos = jnp.int32(0)
        for seg in self.dplan.scan:
            if seg.regime == "plain":
                x, caches = M.decode_range(params, x, caches, pos, cfg,
                                           seg.lo, seg.hi)
                continue
            pol = seg.policy if seg.policy is not None else self.integrity
            start = len(records)
            with L.dense_impl(capture):
                x, caches = M.decode_range_unrolled(
                    params, x, caches, pos, cfg, seg.lo, seg.hi)
            for rec in records[start:]:
                rec["unblinded"] = seg.regime == "verified"
                rec["policy"] = pol
        return records

    def _traced_decode(self, params, token, caches, pos, session_key,
                       factors=None, trusted: bool = False):
        """ONE token step under the decode plan's scan segments.

        ``ctx.step`` is set to the TRACED position, so a single compiled
        executable serves every token of every session while drawing fresh
        per-token pads, fold vectors and sampled-check decisions
        (``fold_in`` accepts traced ints) — and the TokenSlotRing's cached
        factors for ``step == pos`` are bit-identical to this trace's live
        derivation. ``per_op=True`` overrides the scanned-weight inference
        in core/slalom.py: the block walk is unrolled at trace time, so
        each traced dense call stands for exactly one runtime op and
        verification/injection bind per (token, layer). ``params`` feeds
        the open segments, as in ``_run``."""
        tele = SL.Telemetry()
        ctx = SL.SlalomContext(
            session_key, self.spec, telemetry=tele, impl=self.impl,
            factors=factors, integrity=IG.IntegrityPolicy.off(),
            fault=None if trusted else self.fault, trusted=trusted,
            step=pos, per_op=True)
        cfg = self.cfg
        x = M.embed_tokens_at(params, token, pos, cfg)
        for seg in self.dplan.scan:
            if seg.regime == "plain":
                x, caches = M.decode_range(params, x, caches, pos, cfg,
                                           seg.lo, seg.hi)
                continue
            policy = (seg.policy if seg.policy is not None
                      else self.integrity)
            with ExitStack() as stack:
                stack.enter_context(ctx.segment_overrides(
                    policy, unblinded=(seg.regime == "verified"),
                    shard=seg.shard))
                stack.enter_context(L.dense_impl(
                    functools.partial(SL.blinded_dense, ctx)))
                x, caches = M.decode_range_unrolled(
                    self.params, x, caches, pos, cfg, seg.lo, seg.hi)
        logits = M.head(params, x, cfg)
        rep = self._fold_log(ctx)
        if trusted:
            self._tele_trusted = tele
        else:
            self._tele_blinded = tele
        return logits, caches, rep

    def _traced_prefill(self, params, tokens, session_key,
                        trusted: bool = False, *, max_seq: int):
        """Prompt pass through the BASE plan's segments, returning
        ``(last-position logits, decode caches, integrity log)``.

        Offloaded segments run the block walk UNROLLED — per-op
        addressable even at prefill, so every prompt op gets its own
        blinding key and Freivalds fold (no cross-layer pad sharing) —
        while plain segments keep the scanned fast path. Prefill ops use
        ``step=0``; decode steps use ``step=pos >= 1`` (positions count
        from the prompt length), so the two key domains never collide.
        ``params`` feeds the open segments, as in ``_run``."""
        tele = SL.Telemetry()
        ctx = SL.SlalomContext(
            session_key, self.spec, telemetry=tele, impl=self.impl,
            factors=None, integrity=IG.IntegrityPolicy.off(),
            fault=None if trusted else self.fault, trusted=trusted,
            step=0, per_op=True)
        cfg = self.cfg
        x = M.embed_tokens(params, tokens, cfg)
        parts = []
        for seg in self.plan.segments:
            if seg.regime == "plain":
                x, c = M.prefill_range(params, x, cfg, seg.lo, seg.hi)
            else:
                policy = (seg.policy if seg.policy is not None
                          else self.integrity)
                with ExitStack() as stack:
                    stack.enter_context(ctx.segment_overrides(
                        policy, unblinded=(seg.regime == "verified"),
                        shard=seg.shard))
                    stack.enter_context(L.dense_impl(
                        functools.partial(SL.blinded_dense, ctx)))
                    x, c = M.prefill_range_unrolled(self.params, x, cfg,
                                                    seg.lo, seg.hi)
            parts.append(c)
        caches = M.concat_layer_caches(parts, max_seq)
        logits = M.head(params, x[:, -1:], cfg)
        rep = self._fold_log(ctx)
        if trusted:
            self._tele_trusted = tele
        else:
            self._tele_blinded = tele
        return logits, caches, rep

    @staticmethod
    def _fold_log(ctx):
        if ctx.integrity_log:
            return tuple(jnp.stack([e[i] for e in ctx.integrity_log])
                         for i in range(3))
        z = jnp.zeros((0,), jnp.bool_)
        return (z, z, z)

    @staticmethod
    def _cache_seq(caches) -> int:
        for leaf in jax.tree.leaves(caches):
            return int(leaf.shape[2])
        return 0

    def _ensure_decode_exec(self, sig, jfn, traced, kind, args, kw):
        """Decode-plane twin of ``_ensure_executable``: memo -> disk ->
        timed lower+compile, keyed on the DECODE plan digest (distinct
        from the base plan's — DecodePlan.digest covers scan structure)."""
        compiled = self._executables.get(sig)
        if compiled is not None:
            return compiled
        ck = self._aot.entry_key(self.dplan.digest, kind, args)

        def build():
            with tracing.maybe_span("compile.aot", "compile", trace=kind):
                return jfn.lower(*args, **kw).compile(COMPILER_OPTIONS)

        def replay_telemetry():
            with tracing.maybe_span("compile.aot", "compile", trace=kind,
                                    disk_hit=1):
                jax.eval_shape(functools.partial(traced, **kw), *args)

        compiled, _ = self._aot.compile_once(ck, build,
                                             on_disk_hit=replay_telemetry)
        self._executables = {**self._executables, sig: compiled}
        return compiled

    def prefill_session(self, tokens, session_key, *, max_seq: int,
                        trusted: bool = False, jit: bool = True):
        """Public prompt pass: (logits at the last position, decode caches
        padded to ``max_seq``, IntegrityReport over the prefill ops)."""
        assert self.dplan is not None, "attach_decode_plan first"
        kw = {"trusted": trusted, "max_seq": int(max_seq)}
        args = (self.params, tokens, session_key)
        if jit:
            sig = ("prefill", bool(trusted), self.dplan.digest,
                   tuple(tokens.shape), int(max_seq))
            ex = self._ensure_decode_exec(
                sig, self._jit_prefill, self._traced_prefill,
                f"prefill{int(max_seq)}" + ("_trusted" if trusted else ""),
                args, kw)
            logits, caches, rep = ex(*args)
        else:
            logits, caches, rep = self._traced_prefill(*args, **kw)
        self._tele_last = (self._tele_trusted if trusted
                           else self._tele_blinded)
        return logits, caches, IG.IntegrityReport(*rep)

    def decode_once(self, token, caches, pos, session_key, factors=None,
                    *, trusted: bool = False, jit: bool = True):
        """Public single-token step: (logits, updated caches,
        IntegrityReport for this token's offloaded ops). ``factors`` is
        one TokenSlotRing slot (take(token)) or None for the live /
        trusted derivations."""
        assert self.dplan is not None, "attach_decode_plan first"
        pos = jnp.asarray(pos, jnp.int32)
        kw = {"trusted": trusted}
        args = (self.params, token, caches, pos, session_key, factors)
        if jit:
            sig = ("decode", bool(trusted), self.dplan.digest,
                   tuple(token.shape), self._cache_seq(caches),
                   factors is None)
            ex = self._ensure_decode_exec(
                sig, self._jit_decode, self._traced_decode,
                "decode" + ("_trusted" if trusted else ""), args, kw)
            logits, caches, rep = ex(*args)
        else:
            logits, caches, rep = self._traced_decode(*args, **kw)
        self._tele_last = (self._tele_trusted if trusted
                           else self._tele_blinded)
        return logits, caches, IG.IntegrityReport(*rep)

    def warm_decode_aot(self, batch: int, prompt_len: int, max_seq: int,
                        trusted_too: bool = True) -> int:
        """Compile the prefill + token-step executables (and the trusted
        recovery twins) ahead of the first request — the decode analogue
        of ``warm_aot``. Returns the number of signatures ensured."""
        assert self.dplan is not None, "attach_decode_plan first"
        key0 = jax.random.PRNGKey(0)
        tokens = jnp.zeros((batch, int(prompt_len)), jnp.int32)
        token = jnp.zeros((batch, 1), jnp.int32)
        caches = M.init_caches(self.cfg, batch, int(max_seq))
        cache = self.decode_cache(batch)
        n = 0
        with self._aot.warmup_scope():
            for trusted in ((False, True) if trusted_too else (False,)):
                sig = ("prefill", trusted, self.dplan.digest,
                       tuple(tokens.shape), int(max_seq))
                self._ensure_decode_exec(
                    sig, self._jit_prefill, self._traced_prefill,
                    f"prefill{int(max_seq)}"
                    + ("_trusted" if trusted else ""),
                    (self.params, tokens, key0),
                    {"trusted": trusted, "max_seq": int(max_seq)})
                n += 1
                factors = (None if trusted or cache is None
                           else cache.session_factors(key0, 0))
                sig = ("decode", trusted, self.dplan.digest,
                       tuple(token.shape), int(max_seq), factors is None)
                self._ensure_decode_exec(
                    sig, self._jit_decode, self._traced_decode,
                    "decode" + ("_trusted" if trusted else ""),
                    (self.params, token, caches, jnp.int32(prompt_len),
                     key0, factors),
                    {"trusted": trusted})
                n += 1
        return n

    # -- precompute pipeline -------------------------------------------------
    def build_cache(self, batch) -> Optional[BlindedLayerCache]:
        """Quantize/limb-encode every offloaded layer's weights once and
        set up the per-session factor store (DESIGN.md §4).

        The blinded-op records come straight from the plan's static layer
        shapes (``plan.cache_ops`` slots + models/vgg.py shape algebra) —
        no eval_shape re-trace. Forward traces of scanned LM families have
        no cache slots (``plan.cache_ops`` is empty — the typed
        ``plan.ScanExclusion`` domain); their DECODE walk gets per-op
        slots through ``decode_cache``/``_decode_records`` instead
        (DESIGN.md §16).
        """
        ops = self.plan.cache_ops
        if not ops:
            self.precompute = False
            self.cache = None
            return None
        batch_size = int(jnp.shape(batch["images"])[0])
        records = V.blinded_op_records(self.params, self.cfg,
                                       [s.layer_id for s in ops], batch_size)
        for rec, step in zip(records, ops):
            rec["unblinded"] = step.verified_open
            rec["policy"] = (step.integrity if step.integrity is not None
                             else self.integrity)
        self.cache = BlindedLayerCache.from_records(records, self.spec,
                                                    integrity=self.integrity)
        if self._plane_live:
            # prefetch per-shard fold vectors alongside (r, u): the
            # SessionPool ring then keeps shard-local verification material
            # off the request path too
            self.cache.shards = self.plane.n_shards
        shapes = tuple(sorted(
            (k, tuple(jnp.shape(v))) for k, v in batch.items()))
        self._cache_key = (self.plan.digest, shapes)
        # copy-on-write: the SessionPool's refill thread snapshots this
        # dict concurrently; rebinding (vs. in-place insert) keeps any
        # iteration over the old dict safe without a lock
        self._caches = {**self._caches, self._cache_key: self.cache}
        return self.cache

    def prepare_session(self, session_key, step: int = 0) -> None:
        """Prefetch the unblinding factors for a future session so the
        factor matmuls overlap current device compute (serving hook)."""
        if self.cache is not None:
            self.cache.prefetch(session_key, step)

    def _session_factors(self, batch, session_key):
        if not (self.precompute and self.plan.has_offload):
            return None
        shapes = tuple(sorted((k, tuple(jnp.shape(v)))
                              for k, v in batch.items()))
        key = (self.plan.digest, shapes)
        if self.cache is None or key != self._cache_key:
            if key in self._caches:     # recurring shape (padding buckets):
                self.cache = self._caches[key]       # no rebuild thrash
                self._cache_key = key
            else:
                self.build_cache(batch)
        if self.cache is None:          # forward trace has no cache slots
            return None                 # (decode slots: decode_cache())
        return self.cache.take(session_key)

    # -- AOT executables -----------------------------------------------------
    def attach_aot(self, cache: AOT.CompileCache) -> None:
        """Adopt a shared (engine-level) compile cache: cross-executor
        memoization, exactly-once compiles under concurrent registration,
        optional on-disk persistence, and counters in the engine's
        MetricsRegistry. Keeps any executables already compiled."""
        for key, compiled in self._aot._memo.items():
            cache._memo.setdefault(key, compiled)
        self._aot = cache

    def _ensure_executable(self, sig, batch, session_key, factors,
                           trusted: bool):
        """The one compile path: memo -> disk -> timed lower+compile."""
        compiled = self._executables.get(sig)
        if compiled is not None:
            return compiled
        kind = "trusted" if trusted else "blinded"
        jfn = self._aot_jit_trusted if trusted else self._aot_jit
        args = (self.params, batch, session_key, factors)
        ck = self._aot.entry_key(self.plan.digest, kind, args)

        def build():
            with tracing.maybe_span("compile.aot", "compile",
                                    trusted=int(trusted)):
                return jfn.lower(*args).compile(COMPILER_OPTIONS)

        def replay_telemetry():
            # a deserialized executable never runs _traced, so the
            # trace-time telemetry side effects (_tele_blinded/_tele_trusted)
            # would stay stale — replay the trace abstractly (no FLOPs)
            with tracing.maybe_span("compile.aot", "compile",
                                    trusted=int(trusted), disk_hit=1):
                jax.eval_shape(functools.partial(self._traced,
                                                 trusted=trusted), *args)

        compiled, _ = self._aot.compile_once(ck, build,
                                             on_disk_hit=replay_telemetry)
        # copy-on-write rebind: read concurrently by warm (register) and
        # serve (device-stage) threads
        self._executables = {**self._executables, sig: compiled}
        return compiled

    def warm_aot(self, input_key: str, request_shape, buckets,
                 dtype=None, trusted_too: bool = True) -> int:
        """Compile every (trace kind, shape bucket) executable — and build
        the per-bucket factor caches — ahead of the first request.

        Called by ``ServingEngine.register_model``: after this, a request
        only ever hits already-compiled executables (its infer span is
        stamped ``first_call=False``), and the SessionPool prefetches
        sessions into every bucket's cache. The trusted recovery trace is
        warmed too (``trusted_too``) so the §9 recompute ladder and §12
        degraded mode don't pay a first-call compile mid-incident.
        Returns the number of signatures ensured. No-op for offload-plane
        executors (their trace runs eagerly)."""
        if self._plane_live:
            return 0
        key0 = jax.random.PRNGKey(0)
        n = 0
        with self._aot.warmup_scope():
            for b in buckets:
                x = jnp.zeros((int(b),) + tuple(request_shape),
                              dtype if dtype is not None else jnp.float32)
                batch = {input_key: x}
                shapes = tuple(sorted((k, tuple(jnp.shape(v)))
                                      for k, v in batch.items()))
                for trusted in ((False, True) if trusted_too else (False,)):
                    sig = (trusted, self.plan.digest, shapes)
                    factors = (None if trusted
                               else self._session_factors(batch, key0))
                    self._ensure_executable(sig, batch, key0, factors,
                                            trusted)
                    self._seen_sigs.add(sig)
                    n += 1
        return n

    # -- public API ----------------------------------------------------------
    def infer(self, batch: Dict[str, jax.Array],
              session_key: Optional[jax.Array] = None,
              jit: bool = True, trusted: bool = False) -> OrigamiResult:
        """``trusted=True`` runs the enclave-recompute trace: the linear
        ops execute inside the enclave (field matmuls of the enclave's own
        quantized operands), skipping blinding, the untrusted device, the
        fault injector and verification. Bit-identical logits to the honest
        offloaded path — the integrity layer's recovery primitive."""
        key = (session_key if session_key is not None
               else jax.random.PRNGKey(0))
        shapes = tuple(sorted((k, tuple(jnp.shape(v)))
                              for k, v in batch.items()))
        sig = (bool(trusted), self.plan.digest, shapes)
        first_call = sig not in self._seen_sigs
        self._seen_sigs.add(sig)
        shard_report = None
        if trusted:
            ex = self._ensure_executable(sig, batch, key, None, True)
            logits, boundary, rep = ex(self.params, batch, key, None)
        else:
            factors = self._session_factors(batch, key)
            # the plane's host-side dispatch (retry, hedging, per-device
            # health) cannot live inside a jit trace — run eagerly. The
            # field kernels are exact either way; the float tier-2 layers
            # stay bit-identical to the jitted trace for batch >= 2 (XLA
            # picks a different conv algorithm at batch 1), which is the
            # regime the cross-checking drills run in
            if self._plane_live:
                self.plane.begin_infer()
                logits, boundary, rep = self._traced(self.params, batch, key,
                                                     factors)
                shard_report = self.plane.report
            elif jit:
                ex = self._ensure_executable(sig, batch, key, factors,
                                             False)
                logits, boundary, rep = ex(self.params, batch, key, factors)
            else:
                logits, boundary, rep = self._traced(self.params, batch, key,
                                                     factors)
        # the jit cache may skip re-tracing; point the public snapshot at
        # the last trace of THIS kind so a recovery trace never masquerades
        # as an offload trace (or vice versa)
        self._tele_last = (self._tele_trusted if trusted
                           else self._tele_blinded)
        # stamp the ambient infer span (runtime/serving.py opens it around
        # this call) with compile provenance + the cost-model feature
        # quantities this trace moved — what the profiler folds and the
        # CalibratedCostModel fits. Plain ints only (redaction allowlist).
        sp = tracing.current_span()
        if sp is not None:
            tele = self._tele_last
            tracing.annotate(
                sp, first_call=first_call,
                device_flops=int(tele.offloaded_flops),
                enclave_flops=int(tele.enclave_flops),
                blind_bytes=int(tele.blinded_bytes),
                unblind_bytes=int(tele.returned_bytes),
                device_matmuls=int(tele.device_matmuls))
        return OrigamiResult(logits=logits, boundary=boundary,
                             telemetry=self.telemetry,
                             integrity=IG.IntegrityReport(*rep),
                             trusted=trusted, sharding=shard_report)

    def reference(self, batch: Dict[str, jax.Array]) -> jax.Array:
        """Plain fp forward — the correctness oracle for all plans."""
        if self.cfg.family == "cnn":
            return V.vgg_forward(self.params, batch["images"], self.cfg)
        return M.forward(self.params, batch, self.cfg).logits
