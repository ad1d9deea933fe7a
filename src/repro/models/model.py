"""Top-level model API: config -> init / forward / prefill / decode.

``apply_range(params, x, cfg, lo, hi)`` runs blocks [lo, hi) so the Origami
executor can place the tier-1 prefix under the blinded-dense context and run
tier-2 open (core/origami.py). Grouped families (hybrid/ssm/vlm) implement
ranges by slicing their super-block structure.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import attention as A
from repro.models import layers as L
from repro.models import ssm as S
from repro.models import transformer as T
from repro.models.transformer import lm_defs  # re-export
from repro.parallel import act_sharding as act


# ----------------------------------------------------------------------------
# init / specs / counting
# ----------------------------------------------------------------------------

def model_defs(cfg: ModelConfig):
    if cfg.family == "cnn":
        from repro.models.vgg import vgg_defs
        return vgg_defs(cfg)
    return lm_defs(cfg)


def init_params(cfg: ModelConfig, key: jax.Array):
    return L.init_params(key, model_defs(cfg), jnp.dtype(cfg.dtype))


def abstract_params(cfg: ModelConfig):
    """ShapeDtypeStruct pytree — used by the dry-run (no allocation)."""
    return jax.eval_shape(lambda k: init_params(cfg, k),
                          jax.ShapeDtypeStruct((2,), jnp.uint32))


def count_params_analytic(cfg: ModelConfig) -> int:
    return L.param_count(model_defs(cfg))


def active_params_analytic(cfg: ModelConfig) -> int:
    """Activated params per token (MoE: top_k of num_experts)."""
    total = count_params_analytic(cfg)
    if cfg.moe is None:
        return total
    m = cfg.moe
    per_expert = 3 * cfg.d_model * m.d_ff_expert
    inactive = cfg.num_layers * (m.num_experts - m.top_k) * per_expert
    return total - inactive


# ----------------------------------------------------------------------------
# embed / head
# ----------------------------------------------------------------------------

def embed_tokens(params, tokens, cfg: ModelConfig):
    x = L.embed_lookup(params["embed"], tokens).astype(jnp.dtype(cfg.dtype))
    if cfg.family == "audio" or (cfg.attention == "none"
                                 and cfg.rope_theta == 0.0):
        S_ = tokens.shape[-1]
        x = x + L.sinusoidal_positions(S_, cfg.d_model).astype(x.dtype)
    return act.constrain(x, "batch", "seq", "embed_act")


def head(params, x, cfg: ModelConfig):
    x = L.apply_norm(params["final_norm"], x, cfg.norm)
    if cfg.tie_embeddings:
        logits = x @ params["embed"]["table"].astype(x.dtype).T
    else:
        logits = L.dense(params["lm_head"], x)
    return act.constrain(logits, "batch", "seq", "vocab")


# ----------------------------------------------------------------------------
# apply_range per family
# ----------------------------------------------------------------------------

def _range_uniform(params, x, cfg, lo, hi, cost_mode, train):
    blocks = T.slice_layers(params["blocks"], lo, hi)

    def blk(p, h, _):
        return T.decoder_block_fwd(p, h, cfg, cost_mode=cost_mode)

    return T.scan_blocks(blk, blocks, x, cfg, train=train)


def _shared_attn_fwd(p, x, cfg, cost_mode):
    h = x + A.gqa_forward(p["attn"], L.apply_norm(p["ln1"], x, cfg.norm),
                          cfg, cost_mode=cost_mode)
    return h + T.mlp_forward(p["mlp"], L.apply_norm(p["ln2"], h, cfg.norm),
                             cfg)


def _mamba_blk(p, x, cfg):
    return x + S.mamba2_forward(p["mamba"],
                                L.apply_norm(p["norm"], x, cfg.norm), cfg)


def _range_hybrid(params, x, cfg, lo, hi, cost_mode, train):
    e = cfg.hybrid_attn_every
    n_main = (cfg.num_layers // e) * e
    groups = n_main // e

    def scan_mamba(stacked, h):
        def blk(p, h_, _):
            return _mamba_blk(p, h_, cfg), 0.0
        h, _ = T.scan_blocks(blk, stacked, h, cfg, train=train)
        return h

    for g in range(groups):
        g_lo, g_hi = g * e, (g + 1) * e
        a, b = max(lo, g_lo), min(hi, g_hi)
        if a >= b:
            continue
        sub = jax.tree.map(lambda t: t[g], params["mamba_main"])
        x = scan_mamba(T.slice_layers(sub, a - g_lo, b - g_lo), x)
        if b == g_hi and hi >= g_hi:   # group completed inside range
            x = _shared_attn_fwd(params["shared_attn"], x, cfg, cost_mode)
    a, b = max(lo, n_main), min(hi, cfg.num_layers)
    if a < b and "mamba_tail" in params:
        x = scan_mamba(T.slice_layers(params["mamba_tail"],
                                      a - n_main, b - n_main), x)
    return x, 0.0


def _mlstm_blk(p, x, cfg):
    return x + S.mlstm_forward(p["mlstm"],
                               L.apply_norm(p["norm"], x, cfg.norm), cfg)


def _range_xlstm(params, x, cfg, lo, hi, cost_mode, train):
    e = cfg.ssm.slstm_every
    groups = cfg.num_layers // e
    for g in range(groups):
        g_lo = g * e
        a, b = max(lo, g_lo), min(hi, g_lo + e - 1)   # mlstm sub-blocks
        if a < b:
            sub = jax.tree.map(lambda t: t[g], params["mlstm_groups"])

            def blk(p, h, _):
                return _mlstm_blk(p, h, cfg), 0.0
            x, _ = T.scan_blocks(blk, T.slice_layers(sub, a - g_lo, b - g_lo),
                                 x, cfg, train=train)
        sidx = g_lo + e - 1
        if lo <= sidx < hi:
            sp = jax.tree.map(lambda t: t[g], params["slstm_groups"])
            y, _ = S.slstm_forward(sp["slstm"],
                                   L.apply_norm(sp["norm"], x, cfg.norm), cfg)
            x = x + y
    return x, 0.0


def _range_vlm(params, x, cfg, lo, hi, cost_mode, train, patches=None):
    e = cfg.cross_attn_every
    groups = cfg.num_layers // e
    for g in range(groups):
        g_lo = g * e
        a, b = max(lo, g_lo), min(hi, g_lo + e - 1)   # self sub-blocks
        if a < b:
            sub = jax.tree.map(lambda t: t[g], params["self_groups"])

            def blk(p, h, _):
                return T.decoder_block_fwd(p, h, cfg, cost_mode=cost_mode)
            x, _ = T.scan_blocks(blk, T.slice_layers(sub, a - g_lo, b - g_lo),
                                 x, cfg, train=train)
        cidx = g_lo + e - 1
        if lo <= cidx < hi:
            cp = jax.tree.map(lambda t: t[g], params["cross_groups"])
            x = T.vlm_cross_block_fwd(cp, x, patches, cfg,
                                      cost_mode=cost_mode)
    return x, 0.0


def _range_audio_encoder(params, x, cfg, lo, hi, cost_mode, train):
    blocks = T.slice_layers(params["enc_blocks"], lo, hi)

    def blk(p, h, _):
        return T.encoder_block_fwd(p, h, cfg, cost_mode=cost_mode), 0.0

    return T.scan_blocks(blk, blocks, x, cfg, train=train)


def apply_range(params, x, cfg: ModelConfig, lo: int, hi: int, *,
                cost_mode=False, train=False, memory=None):
    """Run blocks [lo, hi) on hidden states x. ``memory`` = patches (vlm)."""
    fam = cfg.family
    if fam in ("dense", "moe"):
        return _range_uniform(params, x, cfg, lo, hi, cost_mode, train)
    if fam == "hybrid":
        return _range_hybrid(params, x, cfg, lo, hi, cost_mode, train)
    if fam == "ssm":
        return _range_xlstm(params, x, cfg, lo, hi, cost_mode, train)
    if fam == "vlm":
        return _range_vlm(params, x, cfg, lo, hi, cost_mode, train,
                          patches=memory)
    if fam == "audio":
        # ranges apply to the encoder prefix (tier-1 ⊆ encoder, DESIGN.md §5)
        return _range_audio_encoder(params, x, cfg, lo, hi, cost_mode, train)
    raise ValueError(fam)


def layer_program(cfg: ModelConfig):
    """(prologue, segment, epilogue) — the LM/audio/vlm layer iterator the
    plan interpreter walks (core/plan.py:program_for).

    Audio plans range over the *encoder* blocks (tier-1 ⊆ encoder — the
    private input is the audio, DESIGN.md §5); the decoder runs in the
    epilogue, always in the clear like the LM head."""
    if cfg.family == "audio":
        def prologue(params, batch):
            frames = batch["frames"].astype(jnp.dtype(cfg.dtype))
            x = frames + L.sinusoidal_positions(
                frames.shape[1], cfg.d_model).astype(frames.dtype)
            return x, None

        def segment(params, x, lo, hi, memory=None):
            x, _ = apply_range(params, x, cfg, lo, hi)
            return x

        def epilogue(params, x, batch, memory=None):
            mem = L.apply_norm(params["enc_norm"], x, cfg.norm)
            return forward_audio_decoder(params, batch, mem, cfg)

        return prologue, segment, epilogue

    def prologue(params, batch):
        memory = batch.get("patches") if cfg.family == "vlm" else None
        return embed_tokens(params, batch["tokens"], cfg), memory

    def segment(params, x, lo, hi, memory=None):
        x, _ = apply_range(params, x, cfg, lo, hi, memory=memory)
        return x

    def epilogue(params, x, batch, memory=None):
        return head(params, x, cfg)

    return prologue, segment, epilogue


# ----------------------------------------------------------------------------
# forward (teacher-forced) per family
# ----------------------------------------------------------------------------

def forward(params, batch: Dict[str, jax.Array], cfg: ModelConfig, *,
            cost_mode=False, train=False) -> T.LMOutputs:
    fam = cfg.family
    if fam == "audio":
        return _forward_audio(params, batch, cfg, cost_mode, train)
    tokens = batch["tokens"]
    x = embed_tokens(params, tokens, cfg)
    memory = batch.get("patches") if fam == "vlm" else None
    x, aux = apply_range(params, x, cfg, 0, cfg.num_layers,
                         cost_mode=cost_mode, train=train, memory=memory)
    return T.LMOutputs(head(params, x, cfg), aux)


def encode_audio(params, frames, cfg: ModelConfig, *, cost_mode=False,
                 train=False):
    x = frames.astype(jnp.dtype(cfg.dtype))
    x = x + L.sinusoidal_positions(x.shape[1], cfg.d_model).astype(x.dtype)
    x, _ = _range_audio_encoder(params, x, cfg, 0, cfg.num_layers,
                                cost_mode, train)
    return L.apply_norm(params["enc_norm"], x, cfg.norm)


def forward_audio_decoder(params, batch, memory, cfg: ModelConfig, *,
                          cost_mode=False, train=False):
    """Decoder over a precomputed encoder memory (Origami tier-2 path)."""
    x = embed_tokens(params, batch["tokens"], cfg)

    def blk(p, h, _):
        return T.cross_decoder_block_fwd(p, h, memory, cfg,
                                         cost_mode=cost_mode), 0.0

    x, _ = T.scan_blocks(blk, params["dec_blocks"], x, cfg, train=train)
    return head(params, x, cfg)


def _forward_audio(params, batch, cfg, cost_mode, train):
    memory = encode_audio(params, batch["frames"], cfg, cost_mode=cost_mode,
                          train=train)
    return T.LMOutputs(
        forward_audio_decoder(params, batch, memory, cfg,
                              cost_mode=cost_mode, train=train), 0.0)


def loss_fn(params, batch, cfg: ModelConfig, aux_weight: float = 0.01):
    out = forward(params, batch, cfg, train=True)
    logits = out.logits[:, :-1]
    labels = batch["tokens"][:, 1:]
    ce = L.cross_entropy(logits, labels, cfg.vocab_size)
    return ce + aux_weight * out.aux_loss, ce


# ----------------------------------------------------------------------------
# caches
# ----------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_seq: int,
                dtype=jnp.bfloat16):
    fam = cfg.family
    hd = cfg.resolved_head_dim

    def kv(n_layers, seq=max_seq, kh=cfg.num_kv_heads):
        return A.KVCache(
            k=jnp.zeros((n_layers, batch, seq, kh, hd), dtype),
            v=jnp.zeros((n_layers, batch, seq, kh, hd), dtype))

    if fam in ("dense", "moe"):
        if cfg.attention == "mla":
            m = cfg.mla
            width = m.kv_lora_rank + m.qk_rope_head_dim
            return A.KVCache(
                k=jnp.zeros((cfg.num_layers, batch, max_seq, width), dtype),
                v=None)
        return kv(cfg.num_layers)
    if fam == "hybrid":
        e = cfg.hybrid_attn_every
        groups = (cfg.num_layers // e)
        tail = cfg.num_layers - groups * e

        def stack_states(n_outer, n_inner=None):
            st = S.mamba2_init_state(cfg, batch)
            def rep(x, n):
                return jnp.broadcast_to(x[None], (n,) + x.shape)
            if n_inner is None:
                return jax.tree.map(lambda x: rep(x, n_outer), st)
            return jax.tree.map(
                lambda x: rep(rep(x, n_inner), n_outer), st)

        caches = {"main": stack_states(groups, e),
                  "shared": kv(groups)}
        if tail:
            caches["tail"] = stack_states(tail)
        return caches
    if fam == "ssm":
        e = cfg.ssm.slstm_every
        groups = cfg.num_layers // e
        mst = S.mlstm_init_state(cfg, batch)
        sst = S.slstm_init_state(cfg, batch)

        def rep(x, n):
            return jnp.broadcast_to(x[None], (n,) + x.shape)
        return {"mlstm": jax.tree.map(
                    lambda x: rep(rep(x, e - 1), groups), mst),
                "slstm": jax.tree.map(lambda x: rep(x, groups), sst)}
    if fam == "audio":
        M_ = cfg.encoder_seq_len
        return {"self": kv(cfg.num_layers),
                "cross_k": jnp.zeros((cfg.num_layers, batch, M_,
                                      cfg.num_kv_heads, hd), dtype),
                "cross_v": jnp.zeros((cfg.num_layers, batch, M_,
                                      cfg.num_kv_heads, hd), dtype)}
    if fam == "vlm":
        e = cfg.cross_attn_every
        groups = cfg.num_layers // e
        M_ = cfg.vision_seq_len
        return {"self": A.KVCache(
                    k=jnp.zeros((groups, e - 1, batch, max_seq,
                                 cfg.num_kv_heads, hd), dtype),
                    v=jnp.zeros((groups, e - 1, batch, max_seq,
                                 cfg.num_kv_heads, hd), dtype)),
                "cross_k": jnp.zeros((groups, batch, M_,
                                      cfg.num_kv_heads, hd), dtype),
                "cross_v": jnp.zeros((groups, batch, M_,
                                      cfg.num_kv_heads, hd), dtype)}
    raise ValueError(fam)


# ----------------------------------------------------------------------------
# prefill
# ----------------------------------------------------------------------------

def prefill(params, batch, cfg: ModelConfig, *, max_seq: Optional[int] = None,
            cost_mode=False):
    """Returns (last-position logits, caches sized to max_seq)."""
    fam = cfg.family
    tokens = batch["tokens"]
    B, S_len = tokens.shape
    max_seq = max_seq or S_len
    cache_dtype = jnp.bfloat16

    def pad_cache(c):
        """Grow stacked prefill caches (L,B,S,...) to (L,B,max_seq,...)."""
        if max_seq == S_len:
            return c
        pad = [(0, 0)] * c.ndim
        pad[2] = (0, max_seq - S_len)
        return jnp.pad(c, pad)

    if fam in ("dense", "moe"):
        x = embed_tokens(params, tokens, cfg)

        def blk(p, h, _):
            h, cache, aux = T.decoder_block_prefill(p, h, cfg,
                                                    cost_mode=cost_mode)
            return h, (cache, aux)

        def body(carry, p_i):
            return blk(p_i, carry, None)

        x, (caches, auxs) = jax.lax.scan(body, x, params["blocks"])
        caches = jax.tree.map(
            lambda c: pad_cache(c.astype(cache_dtype))
            if c is not None else None, caches,
            is_leaf=lambda v: v is None)
        return head(params, x[:, -1:], cfg), caches
    if fam == "audio":
        memory = encode_audio(params, batch["frames"], cfg,
                              cost_mode=cost_mode)
        x = embed_tokens(params, tokens, cfg)

        def body(carry, p_i):
            h, cache = T.cross_decoder_block_prefill(
                p_i, carry, memory, cfg, cost_mode=cost_mode)
            ck, cv = A.cross_kv(p_i["xattn"], memory, cfg)
            return h, (cache, ck, cv)

        x, (caches, cks, cvs) = jax.lax.scan(body, x, params["dec_blocks"])
        return head(params, x[:, -1:], cfg), {
            "self": jax.tree.map(lambda c: pad_cache(c.astype(cache_dtype)),
                                 caches),
            "cross_k": cks.astype(cache_dtype),
            "cross_v": cvs.astype(cache_dtype)}
    # grouped families: prefill == forward + state capture, implemented via
    # their decode-oriented state functions (hybrid/ssm) below.
    raise NotImplementedError(
        f"prefill for family {fam}: use forward() + decode-from-scratch; "
        "assigned prefill cells cover dense/moe/audio/vlm via prefill_vlm")


def prefill_vlm(params, batch, cfg: ModelConfig, *, max_seq=None,
                cost_mode=False):
    tokens, patches = batch["tokens"], batch["patches"]
    B, S_len = tokens.shape
    max_seq = max_seq or S_len
    x = embed_tokens(params, tokens, cfg)
    e = cfg.cross_attn_every
    groups = cfg.num_layers // e
    self_caches, cross_ks, cross_vs = [], [], []
    for g in range(groups):
        sub = jax.tree.map(lambda t: t[g], params["self_groups"])

        def body(carry, p_i):
            h, cache, _ = T.decoder_block_prefill(p_i, carry, cfg,
                                                  cost_mode=cost_mode)
            return h, cache

        x, caches = jax.lax.scan(body, x, sub)
        cp = jax.tree.map(lambda t: t[g], params["cross_groups"])
        x = T.vlm_cross_block_fwd(cp, x, patches.astype(x.dtype), cfg,
                                  cost_mode=cost_mode)
        ck, cv = A.cross_kv(cp["xattn"], patches.astype(x.dtype), cfg)
        self_caches.append(caches)
        cross_ks.append(ck)
        cross_vs.append(cv)

    def pad_cache(c):
        if max_seq == c.shape[2]:
            return c
        pad = [(0, 0)] * c.ndim
        pad[2] = (0, max_seq - c.shape[2])
        return jnp.pad(c, pad)

    stack = lambda xs: jax.tree.map(lambda *a: jnp.stack(a), *xs)
    return head(params, x[:, -1:], cfg), {
        "self": jax.tree.map(lambda c: pad_cache(c.astype(jnp.bfloat16)),
                             stack(self_caches)),
        "cross_k": jnp.stack(cross_ks).astype(jnp.bfloat16),
        "cross_v": jnp.stack(cross_vs).astype(jnp.bfloat16)}


# ----------------------------------------------------------------------------
# decode
# ----------------------------------------------------------------------------

def decode_range(params, x, caches, pos, cfg: ModelConfig,
                 lo: int, hi: int):
    """One-token step through blocks [lo, hi) (dense/moe families)."""
    blocks = T.slice_layers(params["blocks"], lo, hi)
    sub_caches = jax.tree.map(
        lambda c: None if c is None else c[lo:hi], caches,
        is_leaf=lambda v: v is None)

    def body(carry, xs):
        p_i, c_i = xs
        h, c_new = T.decoder_block_decode(p_i, carry, c_i, pos, cfg)
        return h, c_new

    x, new_caches = jax.lax.scan(body, x, (blocks, sub_caches))
    merged = jax.tree.map(
        lambda full, new: jax.lax.dynamic_update_slice_in_dim(
            full, new.astype(full.dtype), lo, axis=0)
        if full is not None else None,
        caches, new_caches, is_leaf=lambda v: v is None)
    return x, merged


def _block_params(params, i: int):
    """Block ``i``'s weights, sliced from the stack. Concrete params give
    concrete slices even under an enclosing trace, so a blinded op's weight
    quantizes exactly as the precompute cache's copy does
    (core/blinding.py:quantize_weight)."""
    with jax.ensure_compile_time_eval():
        return jax.tree.map(lambda t: t[i], params["blocks"])


def decode_range_unrolled(params, x, caches, pos, cfg: ModelConfig,
                          lo: int, hi: int):
    """``decode_range`` with the block walk UNROLLED at trace time
    (dense family): a Python loop over blocks [lo, hi) instead of
    ``lax.scan`` over stacked params.

    Every linear op of every block becomes an individually-addressable
    traced call, which is what lets the decode interpreter
    (core/origami.py) bind per-(token, layer) blinding factors from the
    token-slot ring and run per-step Freivalds verification — the thing
    the scanned walk structurally cannot do (DESIGN.md §16). Numerically
    identical to ``decode_range``; the scanned form stays the fast path
    for plain segments and open generation."""
    new = []
    for i in range(lo, hi):
        p_i = _block_params(params, i)
        c_i = jax.tree.map(lambda c: None if c is None else c[i], caches,
                           is_leaf=lambda v: v is None)
        x, c_new = T.decoder_block_decode(p_i, x, c_i, pos, cfg)
        new.append(c_new)
    stacked = jax.tree.map(lambda *a: jnp.stack(a), *new)
    merged = jax.tree.map(
        lambda full, upd: jax.lax.dynamic_update_slice_in_dim(
            full, upd.astype(full.dtype), lo, axis=0)
        if full is not None else None,
        caches, stacked, is_leaf=lambda v: v is None)
    return x, merged


def prefill_range(params, x, cfg: ModelConfig, lo: int, hi: int, *,
                  cost_mode=False):
    """Prefill blocks [lo, hi) on hidden states x (dense/moe families).

    Returns ``(x, caches)`` with the caches' leading dim = hi - lo — the
    per-segment half of ``prefill``, so the plan interpreter can walk the
    prompt through the base plan's segments (blinded prefix under the
    dense intercept, open suffix without) and still come out with the
    full KV caches the decode loop needs."""
    blocks = T.slice_layers(params["blocks"], lo, hi)

    def body(carry, p_i):
        h, cache, _aux = T.decoder_block_prefill(p_i, carry, cfg,
                                                 cost_mode=cost_mode)
        return h, cache

    return jax.lax.scan(body, x, blocks)


def prefill_range_unrolled(params, x, cfg: ModelConfig, lo: int, hi: int, *,
                           cost_mode=False):
    """``prefill_range`` with the block walk unrolled at trace time —
    the prompt-side twin of ``decode_range_unrolled``: inside a blinded
    plan segment every prompt linear op becomes its own traced call, so
    it draws its own blinding key and Freivalds fold instead of sharing
    one scanned call (and one pad) across layers."""
    cs = []
    for i in range(lo, hi):
        p_i = _block_params(params, i)
        x, cache, _aux = T.decoder_block_prefill(p_i, x, cfg,
                                                 cost_mode=cost_mode)
        cs.append(cache)
    return x, jax.tree.map(lambda *a: jnp.stack(a), *cs)


def concat_layer_caches(parts, max_seq: int, dtype=jnp.bfloat16):
    """Stitch per-segment prefill caches (leading layer dim) back into one
    stack, padded along the sequence axis to ``max_seq`` and cast to the
    decode cache dtype."""
    caches = jax.tree.map(
        lambda *cs: (None if cs[0] is None
                     else jnp.concatenate(cs, axis=0)),
        *parts, is_leaf=lambda v: v is None)

    def pad(c):
        if c is None:
            return None
        if c.shape[2] == max_seq:
            return c.astype(dtype)
        padw = [(0, 0)] * c.ndim
        padw[2] = (0, max_seq - c.shape[2])
        return jnp.pad(c, padw).astype(dtype)

    return jax.tree.map(pad, caches, is_leaf=lambda v: v is None)


def decode_step(params, token, caches, pos, cfg: ModelConfig):
    """token: (B, 1) int32; pos: scalar int32. Returns (logits, caches)."""
    fam = cfg.family
    x = embed_tokens_at(params, token, pos, cfg)
    if fam in ("dense", "moe"):
        x, caches = decode_range(params, x, caches, pos, cfg,
                                 0, cfg.num_layers)
        return head(params, x, cfg), caches
    if fam == "hybrid":
        return _decode_hybrid(params, x, caches, pos, cfg)
    if fam == "ssm":
        return _decode_xlstm(params, x, caches, pos, cfg)
    if fam == "audio":
        return _decode_audio(params, x, caches, pos, cfg)
    if fam == "vlm":
        return _decode_vlm(params, x, caches, pos, cfg)
    raise ValueError(fam)


def embed_tokens_at(params, token, pos, cfg: ModelConfig):
    x = L.embed_lookup(params["embed"], token).astype(jnp.dtype(cfg.dtype))
    if cfg.family == "audio" or (cfg.attention == "none"
                                 and cfg.rope_theta == 0.0):
        d = cfg.d_model
        half = jnp.arange(0, d, 2, dtype=jnp.float32)
        div = jnp.exp(half * (-jnp.log(10000.0) / d))
        ang = pos.astype(jnp.float32) * div
        pe = jnp.zeros((d,), jnp.float32)
        pe = pe.at[0::2].set(jnp.sin(ang)).at[1::2].set(jnp.cos(ang))
        x = x + pe.astype(x.dtype)
    return x


def _decode_hybrid(params, x, caches, pos, cfg):
    e = cfg.hybrid_attn_every
    groups = cfg.num_layers // e
    new_main, new_shared_k, new_shared_v = [], [], []
    for g in range(groups):
        sub_p = jax.tree.map(lambda t: t[g], params["mamba_main"])
        sub_c = jax.tree.map(lambda t: t[g], caches["main"])

        def body(carry, xs):
            p_i, c_i = xs
            y, c_new = S.mamba2_decode(
                p_i["mamba"], L.apply_norm(p_i["norm"], carry, cfg.norm),
                c_i, cfg)
            return carry + y, c_new

        x, c_new = jax.lax.scan(body, x, (sub_p, sub_c))
        new_main.append(c_new)
        sp = params["shared_attn"]
        shared_cache = jax.tree.map(lambda t: t[g], caches["shared"])
        a, sc = A.gqa_decode(sp["attn"], L.apply_norm(sp["ln1"], x, cfg.norm),
                             shared_cache, pos, cfg)
        x = x + a
        x = x + T.mlp_forward(sp["mlp"],
                              L.apply_norm(sp["ln2"], x, cfg.norm), cfg)
        new_shared_k.append(sc.k)
        new_shared_v.append(sc.v)
    out_caches = {
        "main": jax.tree.map(lambda *a: jnp.stack(a), *new_main),
        "shared": A.KVCache(jnp.stack(new_shared_k),
                            jnp.stack(new_shared_v)),
    }
    if "tail" in caches:
        def body(carry, xs):
            p_i, c_i = xs
            y, c_new = S.mamba2_decode(
                p_i["mamba"], L.apply_norm(p_i["norm"], carry, cfg.norm),
                c_i, cfg)
            return carry + y, c_new
        x, c_new = jax.lax.scan(body, x, (params["mamba_tail"],
                                          caches["tail"]))
        out_caches["tail"] = c_new
    return head(params, x, cfg), out_caches


def _decode_xlstm(params, x, caches, pos, cfg):
    e = cfg.ssm.slstm_every
    groups = cfg.num_layers // e
    new_m, new_s = [], []
    for g in range(groups):
        sub_p = jax.tree.map(lambda t: t[g], params["mlstm_groups"])
        sub_c = jax.tree.map(lambda t: t[g], caches["mlstm"])

        def body(carry, xs):
            p_i, c_i = xs
            y, c_new = S.mlstm_decode(
                p_i["mlstm"], L.apply_norm(p_i["norm"], carry, cfg.norm),
                c_i, cfg)
            return carry + y, c_new

        x, c_new = jax.lax.scan(body, x, (sub_p, sub_c))
        new_m.append(c_new)
        sp = jax.tree.map(lambda t: t[g], params["slstm_groups"])
        sc = jax.tree.map(lambda t: t[g], caches["slstm"])
        y, sc_new = S.slstm_forward(
            sp["slstm"], L.apply_norm(sp["norm"], x, cfg.norm), cfg, state=sc)
        x = x + y
        new_s.append(sc_new)
    return head(params, x, cfg), {
        "mlstm": jax.tree.map(lambda *a: jnp.stack(a), *new_m),
        "slstm": jax.tree.map(lambda *a: jnp.stack(a), *new_s)}


def _decode_audio(params, x, caches, pos, cfg):
    def body(carry, xs):
        p_i, c_i, ck, cv = xs
        h, c_new = T.cross_decoder_block_decode(p_i, carry, ck, cv, c_i,
                                                pos, cfg)
        return h, c_new

    x, new_self = jax.lax.scan(
        body, x, (params["dec_blocks"], caches["self"],
                  caches["cross_k"], caches["cross_v"]))
    return head(params, x, cfg), {**caches, "self": new_self}


def _decode_vlm(params, x, caches, pos, cfg):
    e = cfg.cross_attn_every
    groups = cfg.num_layers // e
    new_selfs = []
    for g in range(groups):
        sub_p = jax.tree.map(lambda t: t[g], params["self_groups"])
        sub_c = jax.tree.map(lambda t: t[g], caches["self"])

        def body(carry, xs):
            p_i, c_i = xs
            h, c_new = T.decoder_block_decode(p_i, carry, c_i, pos, cfg)
            return h, c_new

        x, c_new = jax.lax.scan(body, x, (sub_p, sub_c))
        new_selfs.append(c_new)
        cp = jax.tree.map(lambda t: t[g], params["cross_groups"])
        x = T.vlm_cross_block_cached(cp, x, caches["cross_k"][g],
                                     caches["cross_v"][g], cfg)
    return head(params, x, cfg), {
        **caches,
        "self": jax.tree.map(lambda *a: jnp.stack(a), *new_selfs)}
