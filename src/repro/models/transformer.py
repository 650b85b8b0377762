"""Decoder / encoder transformer covering the dense, moe, vlm and audio
families (command-r, danube3, phi3, stablelm, grok-1, dbrx, internvl2,
hubert) with GQA, RoPE, SwiGLU, sliding windows, parallel blocks, MoE FFNs,
modality-stub inputs, KV caches — all softmax/exp paths through VEXP.

Layers are stacked along a leading axis and executed with jax.lax.scan
(compile-time and HLO-size critical at 40-64 layers); each layer body is
optionally rematerialized.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.analysis.registry import hot_path
from repro.core.attention import attention, decode_attention, masked_policy
from .layers import (dense_init, embed_init, norm_init, norm_apply,
                     apply_rope, mlp_init, mlp_apply, cross_entropy,
                     mask_padded_logits)
from .moe import moe_init, moe_apply


def _cdtype(cfg):
    return jnp.dtype(cfg.compute_dtype)


# ------------------------------------------------------------------ attention

def attn_init(key, cfg, dtype=jnp.float32):
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], d, h * hd, dtype),
         "wk": dense_init(ks[1], d, hkv * hd, dtype),
         "wv": dense_init(ks[2], d, hkv * hd, dtype),
         "wo": dense_init(ks[3], h * hd, d, dtype)}
    if cfg.use_bias:
        p["bq"] = jnp.zeros((h * hd,), dtype)
        p["bk"] = jnp.zeros((hkv * hd,), dtype)
        p["bv"] = jnp.zeros((hkv * hd,), dtype)
    return p


def _qkv(x, p, cfg, pos):
    b, s, _ = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    q = x @ p["wq"]
    k = x @ p["wk"]
    v = x @ p["wv"]
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    q = q.reshape(b, s, h, hd)
    k = k.reshape(b, s, hkv, hd)
    v = v.reshape(b, s, hkv, hd)
    if cfg.rope_pct > 0:
        q = apply_rope(q, pos, cfg.rope_theta, cfg.rope_pct)
        k = apply_rope(k, pos, cfg.rope_theta, cfg.rope_pct)
    return q, k, v


def attn_apply(x, p, cfg, pos, *, window=None, causal=None, kv_valid=None,
               policy=None):
    """Full-sequence attention (train / prefill). Returns y, (k, v).

    ``policy`` (an ExecPolicy) selects exp backend + kernel backend +
    blocks; when None the cfg's legacy fields apply unchanged.
    ``kv_valid`` (B, S) masks padded prompt positions out of the keys.
    """
    causal = cfg.causal if causal is None else causal
    q, k, v = _qkv(x, p, cfg, pos)
    if kv_valid is not None:
        policy = masked_policy(policy)
    with jax.named_scope("attn"):
        o = attention(q, k, v, causal=causal, window=window,
                      exp_impl=cfg.exp_impl, impl=cfg.attention_impl,
                      unroll=cfg.unroll_scans, block_k=cfg.attn_block_k,
                      mm_dtype=cfg.attn_mm_dtype, kv_valid=kv_valid,
                      policy=policy)
    return o.reshape(x.shape[0], x.shape[1], -1) @ p["wo"], (k, v)


def cache_seq_axis(layout: str, stacked: bool = True) -> int:
    """Index of the sequence axis in a KV cache of the given layout.

    Stacked caches are (L, B, S, Hkv*hd) for "bshd" and (L, B, Hkv, S, hd)
    for "bhsd"; per-layer caches drop the leading L. Resolving the axis
    here (instead of hardcoding -3, which is only correct for "bshd")
    keeps every cache pad/insert site layout-correct.
    """
    if layout not in ("bshd", "bhsd"):
        raise ValueError(f"unknown kv cache layout {layout!r}")
    base = 1 if layout == "bshd" else 2
    return base + (1 if stacked else 0)


def cache_axes(cfg):
    """DecodeState leaf metadata: slot axis + layout-resolved sequence
    axis of each stacked KV-cache leaf (the slot engine's scatter spec)."""
    from .state_spec import LeafAxes
    ax = cache_seq_axis(cfg.kv_cache_layout)
    return {"k": LeafAxes(1, ax), "v": LeafAxes(1, ax)}


def _rope_pos(b, pos):
    """(B, 1) rope positions from a scalar or per-row (B,) position."""
    pos = jnp.asarray(pos, jnp.int32)
    if pos.ndim == 1:
        return pos[:, None]
    return jnp.full((b, 1), pos, jnp.int32)


def _write_token_kv(cache, kv, pos, layout, *, oob_drop=False, layer=None):
    """Write one token's K (or V) into the cache at ``pos``.

    kv: (B, 1, Hkv, hd) for "bshd" / (B, Hkv, 1, hd) for "bhsd"; a
    lane-dense (B, S, Hkv*hd) "bshd" cache takes it as one (B, 1, Hkv*hd)
    row. ``pos`` scalar writes one slice (dynamic_update_slice); a per-slot
    (B,) vector scatters each row at its own position, so ragged slots in
    a continuous batch never touch each other's cache rows. With ``layer``
    (traced int32 scalar) ``cache`` is the stacked pool and the rows land
    in that layer, in place: nothing of the pool is sliced or copied.

    ``oob_drop`` makes out-of-range rows drop instead of clamp — the
    sequence-sharded decode path hands every shard the same write with
    *local* positions, and only the shard whose slice contains the token
    may land it (vector ``pos`` only). ``mode="drop"`` alone is not
    enough: scatter indices in ``[-S, 0)`` would *wrap* numpy-style
    before the drop logic sees them, so shards below the owner would
    land spurious rows — remap every out-of-slice position to S (a
    genuinely droppable index) first.
    """
    lead = () if layer is None else (jnp.asarray(layer, jnp.int32),)
    one = cache.shape[len(lead):]            # one layer's cache
    kv = kv.astype(cache.dtype)
    if len(one) == 3:
        kv = kv.reshape(kv.shape[0], 1, -1)
    ax = len(lead) + (2 if layout == "bhsd" else 1)
    if jnp.ndim(pos) == 0:
        assert not oob_drop, "oob_drop needs a per-row position vector"
        start = [jnp.int32(0)] * cache.ndim
        start[ax] = jnp.asarray(pos, jnp.int32)
        start[:len(lead)] = lead
        return jax.lax.dynamic_update_slice(
            cache, kv.reshape((1,) * len(lead) + kv.shape), start)
    kw = {}
    if oob_drop:
        s = cache.shape[ax]
        pos = jnp.where((pos >= 0) & (pos < s), pos, s)
        kw = {"mode": "drop"}
    b = one[0]
    if layout == "bhsd":
        hkv = one[1]
        return cache.at[lead + (jnp.arange(b)[:, None],
                                jnp.arange(hkv)[None, :],
                                pos[:, None])].set(kv[:, :, 0], **kw)
    return cache.at[lead + (jnp.arange(b), pos)].set(kv[:, 0], **kw)


def attn_decode(x, p, cfg, cache_k, cache_v, pos, *, window=None,
                policy=None, write_pos=None, oob_drop=False, layer=None):
    """Single-token decode. cache_[kv]: (B, Smax, Hkv*hd) for "bshd"
    layout, (B, Hkv, Smax, hd) for "bhsd", or with ``layer`` the stacked
    pool of them, written and read at that layer; pos: scalar int or
    per-slot (B,) vector of current positions. Returns y, (new_k_cache,
    new_v_cache).

    ``write_pos`` (with ``oob_drop``) splits the write coordinate from the
    attention position: the serving engine parks dead / mid-chunk-prefill
    slots at a droppable sentinel so the step never mutates their cache
    rows while still computing (discarded) attention for them."""
    b = x.shape[0]
    lay = cfg.kv_cache_layout
    q, k, v = _qkv(x, p, cfg, _rope_pos(b, pos))
    if lay == "bhsd":
        k = k.transpose(0, 2, 1, 3)          # (B, Hkv, 1, hd) — tiny
        v = v.transpose(0, 2, 1, 3)
    wp = pos if write_pos is None else write_pos
    with jax.named_scope("kv_write"):
        ck = _write_token_kv(cache_k, k, wp, lay, oob_drop=oob_drop,
                             layer=layer)
        cv = _write_token_kv(cache_v, v, wp, lay, oob_drop=oob_drop,
                             layer=layer)
    with jax.named_scope("attn"):
        o = decode_attention(q, ck, cv, cache_len=pos + 1, window=window,
                             exp_impl=cfg.exp_impl,
                             mm_dtype=cfg.attn_mm_dtype, layout=lay,
                             policy=policy, layer=layer)
    return o.reshape(b, 1, -1) @ p["wo"], (ck, cv)


# Droppable write sentinel for dead / mid-chunk-prefill slots: far above
# any cache extent, so an oob_drop scatter (which remaps >= S to the
# droppable index) never lands it. Must be applied AFTER any ring-buffer
# wrap — a post-modulo position is always in range.
PARKED_POS = jnp.int32(1 << 30)


def attn_decode_sharded(x, p, cfg, cache_k, cache_v, pos, *, seq_axis,
                        policy, write_pos=None):
    """Single-token decode over a sequence-sharded KV cache (call INSIDE
    ``shard_map``). ``cache_[kv]`` are each shard's *local* S-slice; every
    shard computes the token's K/V (tiny, replicated work), lands it with
    an out-of-bounds-dropping scatter at its local position — so exactly
    the shard whose slice contains ``pos`` writes — and sweeps its slice
    in partial-statistics mode; the shards fold through the policy's
    merge strategy (one packed all_gather, or pmax + 2×psum). The only
    collective of the whole step is that merge."""
    b = x.shape[0]
    lay = cfg.kv_cache_layout
    q, k, v = _qkv(x, p, cfg, _rope_pos(b, pos))
    if lay == "bhsd":
        k = k.transpose(0, 2, 1, 3)
        v = v.transpose(0, 2, 1, 3)
    s_ax = cache_seq_axis(lay, stacked=False)
    local_s = cache_k.shape[s_ax]
    off = jax.lax.axis_index(seq_axis) * local_s
    gpos = jnp.asarray(pos, jnp.int32)
    gw = gpos if write_pos is None else jnp.asarray(write_pos, jnp.int32)
    lpos = jnp.broadcast_to(gw.reshape(-1), (b,)) - off
    ck = _write_token_kv(cache_k, k, lpos, lay, oob_drop=True)
    cv = _write_token_kv(cache_v, v, lpos, lay, oob_drop=True)
    from repro.kernels.decode_attention.ops import \
        decode_attention_partial_merged
    o = decode_attention_partial_merged(
        q, ck, cv, gpos + 1, off, seq_axis=seq_axis, layout=lay,
        policy=policy)
    return o.reshape(b, 1, -1) @ p["wo"], (ck, cv)


def _attn_apply_hist(x, p, cfg, pos, hk, hv, *, suffix_valid=None,
                     policy=None):
    """Suffix attention against a prepended KV history (paged prefix-cache
    hot path): queries are the suffix tokens at absolute positions ``pos``
    (already offset by the history length), keys/values are
    ``[history | suffix]``. ``hk``/``hv`` (B, h, Hkv, hd) hold the shared
    prefix's already-roped KV gathered from the pool — always "bshd"
    regardless of ``cfg.kv_cache_layout``. Returns y and the *suffix-only*
    (k, v) (the prefix pages already exist; only the suffix is scattered
    back). The ``q_offset``/``kv_valid`` masks run under
    ``masked_policy`` — prefix-hot prefill is rare and short."""
    b, s, _ = x.shape
    h = hk.shape[1]
    q, k, v = _qkv(x, p, cfg, pos)
    kcat = jnp.concatenate([hk.astype(k.dtype), k], axis=1)
    vcat = jnp.concatenate([hv.astype(v.dtype), v], axis=1)
    kv_valid = None
    if suffix_valid is not None:
        kv_valid = jnp.concatenate(
            [jnp.ones((b, h), bool), suffix_valid], axis=1)
    o = attention(q, kcat, vcat, causal=True, window=None, q_offset=h,
                  exp_impl=cfg.exp_impl, impl=cfg.attention_impl,
                  unroll=cfg.unroll_scans, block_k=cfg.attn_block_k,
                  mm_dtype=cfg.attn_mm_dtype, kv_valid=kv_valid,
                  policy=masked_policy(policy))
    return o.reshape(b, s, -1) @ p["wo"], (k, v)


# --------------------------------------------------------------------- block

def block_init(key, cfg, dtype=jnp.float32):
    ks = jax.random.split(key, 4)
    p = {"ln_attn": norm_init(cfg.d_model, cfg.norm),
         "attn": attn_init(ks[0], cfg, dtype)}
    if not cfg.parallel_block:
        p["ln_mlp"] = norm_init(cfg.d_model, cfg.norm)
    if cfg.n_experts:
        p["moe"] = moe_init(ks[1], cfg, dtype)
    else:
        p["mlp"] = mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act,
                            cfg.use_bias, dtype)
    return p


def block_apply(x, p, cfg, pos, *, kv_valid=None, policy=None):
    """Returns (y, kv, aux)."""
    aux = {}
    h = norm_apply(x, p["ln_attn"], cfg.norm, cfg.norm_eps)
    a, kv = attn_apply(h, p["attn"], cfg, pos, window=cfg.sliding_window,
                       kv_valid=kv_valid, policy=policy)
    if cfg.parallel_block:
        # command-r: attention and FFN read the same normed input.
        if cfg.n_experts:
            m, aux = moe_apply(h, p["moe"], cfg)
        else:
            m = mlp_apply(h, p["mlp"], cfg.act, cfg.exp_impl, policy=policy)
        return x + a + m, kv, aux
    x = x + a
    h = norm_apply(x, p["ln_mlp"], cfg.norm, cfg.norm_eps)
    if cfg.n_experts:
        m, aux = moe_apply(h, p["moe"], cfg)
    else:
        m = mlp_apply(h, p["mlp"], cfg.act, cfg.exp_impl, policy=policy)
    return x + m, kv, aux


def block_apply_hist(x, p, cfg, pos, hk, hv, *, suffix_valid=None,
                     policy=None):
    """``block_apply`` with a prepended KV history (see _attn_apply_hist).
    Returns (y, suffix_kv)."""
    h = norm_apply(x, p["ln_attn"], cfg.norm, cfg.norm_eps)
    a, kv = _attn_apply_hist(h, p["attn"], cfg, pos, hk, hv,
                             suffix_valid=suffix_valid, policy=policy)
    return _finish_block(x, h, a, p, cfg, policy=policy), kv


def block_decode(x, p, cfg, cache_k, cache_v, pos, *, policy=None):
    h = norm_apply(x, p["ln_attn"], cfg.norm, cfg.norm_eps)
    a, kv = attn_decode(h, p["attn"], cfg, cache_k, cache_v, pos,
                        window=cfg.sliding_window, policy=policy)
    if cfg.parallel_block:
        if cfg.n_experts:
            m, _ = moe_apply(h, p["moe"], cfg)
        else:
            m = mlp_apply(h, p["mlp"], cfg.act, cfg.exp_impl, policy=policy)
        return x + a + m, kv
    x = x + a
    h = norm_apply(x, p["ln_mlp"], cfg.norm, cfg.norm_eps)
    if cfg.n_experts:
        m, _ = moe_apply(h, p["moe"], cfg)
    else:
        m = mlp_apply(h, p["mlp"], cfg.act, cfg.exp_impl, policy=policy)
    return x + m, kv


# ---------------------------------------------------------------- full model

def init_params(cfg, key):
    ks = jax.random.split(key, cfg.n_layers + 4)
    layers = [block_init(ks[i], cfg) for i in range(cfg.n_layers)]
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *layers)
    p = {"layers": stacked,
         "ln_f": norm_init(cfg.d_model, cfg.norm)}
    if cfg.family == "audio":
        # HuBERT's conv feature extractor and conv-relative positional
        # embedding are stubbed (precomputed frames + sinusoidal positions,
        # length-agnostic for the 32k-frame prefill shape).
        p["in_proj"] = dense_init(ks[-1], cfg.frame_input_dim, cfg.d_model)
        p["unembed"] = dense_init(ks[-3], cfg.d_model, cfg.vocab_padded)
        return p
    p["embed"] = embed_init(ks[-1], cfg.vocab_padded, cfg.d_model)
    if not cfg.tie_embeddings:
        p["unembed"] = dense_init(ks[-2], cfg.d_model, cfg.vocab_padded)
    if cfg.family == "vlm":
        p["vis_proj"] = dense_init(ks[-3], cfg.vision_embed_dim, cfg.d_model)
    return p


def unembed_matrix(params, cfg):
    return (params["embed"].T if cfg.tie_embeddings
            else params["unembed"])


def embed_inputs(params, cfg, tokens, extra=None):
    """tokens (B, S_txt) int32; extra: vlm vision embeds (B, Nv, Dv) or
    audio frames (B, S, F). Returns (B, S, D) in compute dtype."""
    dt = _cdtype(cfg)
    if cfg.family == "audio":
        x = extra.astype(dt) @ params["in_proj"].astype(dt)
        s, d = x.shape[1], x.shape[2]
        pos = jnp.arange(s, dtype=jnp.float32)[:, None]
        inv = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, jnp.float32) / d))
        pe = jnp.concatenate([jnp.sin(pos * inv), jnp.cos(pos * inv)], -1)
        return x + pe.astype(dt)[None]
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    if cfg.family == "vlm" and extra is not None:
        vis = extra.astype(dt) @ params["vis_proj"].astype(dt)
        x = jnp.concatenate([vis, x], axis=1)
    return x


def forward(params, cfg, tokens, extra=None, pos=None, *, policy=None):
    """Full-sequence forward to final hidden states (B, S, D) + aux."""
    x = embed_inputs(params, cfg, tokens, extra)
    b, s, _ = x.shape
    if pos is None:
        pos = jnp.arange(s)[None, :].astype(jnp.int32)
    dt = _cdtype(cfg)

    def body(carry, layer_p):
        x, aux_acc = carry
        layer_p = jax.tree.map(lambda a: a.astype(dt)
                               if a.dtype == jnp.float32 and a.ndim > 1
                               else a, layer_p)
        y, _, aux = block_apply(x, layer_p, cfg, pos, policy=policy)
        if aux:
            aux_acc = {k: aux_acc.get(k, 0.0) + v for k, v in aux.items()}
        return (y, aux_acc), None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    aux0 = ({"moe_aux": jnp.float32(0), "moe_z": jnp.float32(0)}
            if cfg.n_experts else {})
    (x, aux), _ = jax.lax.scan(body, (x, aux0), params["layers"],
                               unroll=cfg.n_layers if cfg.unroll_scans else 1)
    x = norm_apply(x, params["ln_f"], cfg.norm, cfg.norm_eps)
    return x, aux


def loss_fn(params, cfg, batch, *, policy=None):
    """Training loss. batch: {"tokens", "labels", optional "extra"}."""
    x, aux = forward(params, cfg, batch["tokens"], batch.get("extra"),
                     policy=policy)
    labels = batch["labels"]
    mask = batch.get("mask")
    if cfg.family == "vlm" and batch.get("extra") is not None:
        x = x[:, batch["extra"].shape[1]:]       # loss on text positions only
    w = unembed_matrix(params, cfg)
    loss = cross_entropy(x, w, labels, chunk=cfg.loss_chunk,
                         exp_impl=cfg.exp_impl,
                         logit_softcap=cfg.logit_softcap, mask=mask,
                         unroll=cfg.unroll_scans, policy=policy)
    for v in (aux or {}).values():
        loss = loss + v / cfg.n_layers
    return loss


def init_cache(cfg, batch, seq_len, dtype=jnp.bfloat16):
    """Stacked KV cache: (L, B, S, Hkv*hd) ("bshd") or (L, B, Hkv, S, hd)
    ("bhsd") ×2. "bshd" folds the heads into the lanes, the form the
    flash-decode kernel reads with no copy (``kernels.decode_attention``).
    Windowed archs allocate only the window (ring-buffer semantics handled
    by position clamping)."""
    s = min(seq_len, cfg.sliding_window) if cfg.sliding_window else seq_len
    if cfg.kv_cache_layout == "bhsd":
        shape = (cfg.n_layers, batch, cfg.n_kv_heads, s, cfg.hd)
    else:
        shape = (cfg.n_layers, batch, s, cfg.n_kv_heads * cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def prefill(params, cfg, tokens, extra=None, *, prompt_len=None, policy=None,
            hist=None):
    """Forward over the prompt; returns (last_logits, cache).

    ``prompt_len`` (B,) enables ragged right-padded batches: tokens beyond
    each row's length are padding — they are masked out of attention (no
    real token attends a pad, no pad pollutes the softmax normalizer),
    their K/V cache rows are zeroed, and the returned logits are each
    row's *last real* position (not the padded tail). Without it, every
    row is assumed full-length (the previous behaviour, unchanged).

    ``hist`` enables *suffix* prefill against a shared-prefix KV history
    (the paged engine's prefix-cache hot path): a stacked
    {"k": (L, B, h, Hkv, hd), "v": ...} of already-computed history KV
    (always "bshd", bf16). ``tokens`` are then only each row's suffix,
    attending causally over ``[history | suffix]`` at absolute positions
    ``h + i``; ``prompt_len`` counts *suffix* tokens; the returned cache
    and logits cover the suffix only. Linear caches only — a windowed
    arch's ring roll has no meaningful history split.
    """
    if prompt_len is not None and extra is not None:
        raise ValueError("prompt_len is only supported for token-only "
                         "prefill (no vlm/audio extra inputs)")
    if hist is not None and (extra is not None or cfg.sliding_window):
        raise ValueError("history-conditioned prefill requires a token-only "
                         "arch with a linear (non-windowed) cache")
    x = embed_inputs(params, cfg, tokens, extra)
    b, s, _ = x.shape
    if (prompt_len is not None and cfg.sliding_window
            and s > cfg.sliding_window):
        raise ValueError(
            f"ragged prefill of {s} tokens exceeds the sliding window "
            f"({cfg.sliding_window}): the ring-buffer roll is batch-"
            f"uniform; prefill ragged windowed batches at <= window")
    hlen = 0 if hist is None else hist["k"].shape[2]
    pos = (jnp.arange(s) + hlen)[None, :].astype(jnp.int32)
    kv_valid = None
    if prompt_len is not None:
        plen = jnp.asarray(prompt_len, jnp.int32).reshape(-1)
        kv_valid = jnp.arange(s)[None, :] < plen[:, None]        # (B, S)
    dt = _cdtype(cfg)

    def body(x, inp):
        layer_p = inp if hist is None else inp[0]
        layer_p = jax.tree.map(lambda a: a.astype(dt)
                               if a.dtype == jnp.float32 and a.ndim > 1
                               else a, layer_p)
        if hist is None:
            y, kv, _ = block_apply(x, layer_p, cfg, pos, kv_valid=kv_valid,
                                   policy=policy)
        else:
            y, kv = block_apply_hist(x, layer_p, cfg, pos, inp[1], inp[2],
                                     suffix_valid=kv_valid, policy=policy)
        k, v = kv
        if kv_valid is not None:
            # pad rows must not reach the decode cache: decode masks by
            # cache_len, but zeroing keeps freed/reused slots hygienic.
            k = jnp.where(kv_valid[:, :, None, None], k, 0)
            v = jnp.where(kv_valid[:, :, None, None], v, 0)
        if cfg.sliding_window and s > cfg.sliding_window:
            w = cfg.sliding_window
            # ring-buffer layout: absolute position p lives at slot p % w,
            # matching decode_step's write cursor.
            k = jnp.roll(k[:, -w:], s % w, axis=1)
            v = jnp.roll(v[:, -w:], s % w, axis=1)
        if cfg.kv_cache_layout == "bhsd":
            k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        else:                                    # init_cache's lane-dense form
            k, v = k.reshape(*k.shape[:2], -1), v.reshape(*v.shape[:2], -1)
        return y, {"k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    xs = (params["layers"] if hist is None
          else (params["layers"], hist["k"], hist["v"]))
    x, cache = jax.lax.scan(body, x, xs,
                            unroll=cfg.n_layers if cfg.unroll_scans else 1)
    x = norm_apply(x, params["ln_f"], cfg.norm, cfg.norm_eps)
    if prompt_len is None:
        xl = x[:, -1:]
    else:
        idx = jnp.clip(plen - 1, 0, s - 1)[:, None, None]
        xl = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
    ldt = jnp.bfloat16 if cfg.logits_mm_dtype == "bf16" else jnp.float32
    logits = jnp.einsum("bsd,dv->bsv", xl.astype(ldt),
                        unembed_matrix(params, cfg).astype(ldt),
                        preferred_element_type=jnp.float32)
    return mask_padded_logits(logits, cfg.vocab), cache


# ------------------------------------------------------------ chunked prefill

def _write_chunk_kv(cache, kv, rows, layout):
    """Scatter a C-token chunk into per-row cache positions.

    kv: (B, C, Hkv, hd); rows: (B, C) absolute cache positions with
    invalid lanes pre-remapped to S (a droppable index); cache is one
    layer's slot pool row block — (B, S, Hkv*hd) or (B, S, Hkv, hd) "bshd"
    / (B, Hkv, S, hd) "bhsd"."""
    kv = kv.astype(cache.dtype)
    b, c = rows.shape
    if cache.ndim == 3:
        kv = kv.reshape(b, c, -1)
    if layout == "bhsd":
        hkv = cache.shape[1]
        return cache.at[jnp.arange(b)[:, None, None],
                        jnp.arange(hkv)[None, :, None],
                        rows[:, None, :]].set(kv.transpose(0, 2, 1, 3),
                                              mode="drop")
    return cache.at[jnp.arange(b)[:, None], rows].set(kv, mode="drop")


def _attn_chunk(x, p, cfg, ck, cv, off, clens, *, policy=None):
    """Chunk-prefill attention: write the chunk's K/V into the slot cache
    at per-row cursor offsets, then attend the Q-chunk causally over the
    *updated* cache — already-cached prefix and intra-chunk keys in one
    sweep, masked by per-row ``q_offset``/``kv_valid`` (the flash path;
    no new kernel). Returns y, (ck, cv)."""
    b, c, _ = x.shape
    lay = cfg.kv_cache_layout
    s = ck.shape[cache_seq_axis(lay, stacked=False)]
    pos = off[:, None] + jnp.arange(c)[None, :]            # (B, C)
    q, k, v = _qkv(x, p, cfg, pos)
    lane = jnp.arange(c)[None, :] < clens[:, None]         # (B, C)
    k = jnp.where(lane[:, :, None, None], k, 0)            # pad hygiene
    v = jnp.where(lane[:, :, None, None], v, 0)
    rows = jnp.where(lane, pos, s)                         # invalid -> drop
    ck = _write_chunk_kv(ck, k, rows, lay)
    cv = _write_chunk_kv(cv, v, rows, lay)
    if lay == "bshd":
        kk, vv = (x.reshape(b, s, cfg.n_kv_heads, cfg.hd) for x in (ck, cv))
    else:
        kk, vv = ck.transpose(0, 2, 1, 3), cv.transpose(0, 2, 1, 3)
    # stale rows of a reused slot (and rows beyond this row's progress)
    # are masked out of both weights and normalizer.
    kv_valid = jnp.arange(s)[None, :] < (off + clens)[:, None]
    o = attention(q, kk, vv, causal=True, window=cfg.sliding_window,
                  q_offset=off, exp_impl=cfg.exp_impl,
                  impl=cfg.attention_impl, unroll=cfg.unroll_scans,
                  block_k=cfg.attn_block_k, mm_dtype=cfg.attn_mm_dtype,
                  kv_valid=kv_valid, policy=masked_policy(policy))
    return o.reshape(b, c, -1) @ p["wo"], (ck, cv)


def _chunk_logits(params, cfg, x, clens):
    """Last-valid-lane logits of a chunk program: (B, 1, V)."""
    x = norm_apply(x, params["ln_f"], cfg.norm, cfg.norm_eps)
    b, c, d = x.shape
    idx = jnp.clip(clens - 1, 0, c - 1)[:, None, None]
    xl = jnp.take_along_axis(x, jnp.broadcast_to(idx, (b, 1, d)), axis=1)
    ldt = jnp.bfloat16 if cfg.logits_mm_dtype == "bf16" else jnp.float32
    logits = jnp.einsum("bsd,dv->bsv", xl.astype(ldt),
                        unembed_matrix(params, cfg).astype(ldt),
                        preferred_element_type=jnp.float32)
    return mask_padded_logits(logits, cfg.vocab)


def _chunk_all_logits(params, cfg, x):
    """Every-lane logits of a chunk program: (B, C, V). The batched
    speculative verify scores all k+1 candidate tokens from one chunk
    pass; lanes at or past a row's ``clens`` carry garbage the caller
    masks out of acceptance."""
    x = norm_apply(x, params["ln_f"], cfg.norm, cfg.norm_eps)
    ldt = jnp.bfloat16 if cfg.logits_mm_dtype == "bf16" else jnp.float32
    logits = jnp.einsum("bsd,dv->bsv", x.astype(ldt),
                        unembed_matrix(params, cfg).astype(ldt),
                        preferred_element_type=jnp.float32)
    return mask_padded_logits(logits, cfg.vocab)


def prefill_chunk(params, cfg, tokens, cache, off, clens, *, policy=None,
                  all_lanes=False):
    """Resumable prefill: advance every prefilling slot by one fixed-size
    chunk, writing chunk KV directly into the slot-pool cache carry.

    tokens (B, C) int32; cache: the *pool* stacked KV (all slots); off
    (B,) per-slot progress cursors (tokens already cached); clens (B,)
    valid tokens in this chunk — 0 marks rows not prefilling this tick
    (decoding / free slots), which pass through bit-untouched. Returns
    (logits, cache): logits are each row's last-valid-lane next-token
    distribution, meaningful only for rows whose prompt completes with
    this chunk (off + clens == prompt_len). ``all_lanes=True``
    (speculative verify) returns (B, C, V) logits for every lane instead
    — lanes >= clens are garbage the caller masks."""
    x = embed_inputs(params, cfg, tokens)
    off = jnp.asarray(off, jnp.int32).reshape(-1)
    clens = jnp.asarray(clens, jnp.int32).reshape(-1)
    dt = _cdtype(cfg)

    def body(x, inp):
        layer_p, ck, cv = inp
        layer_p = jax.tree.map(lambda a: a.astype(dt)
                               if a.dtype == jnp.float32 and a.ndim > 1
                               else a, layer_p)
        h = norm_apply(x, layer_p["ln_attn"], cfg.norm, cfg.norm_eps)
        a, (ck, cv) = _attn_chunk(h, layer_p["attn"], cfg, ck, cv, off,
                                  clens, policy=policy)
        x = _finish_block(x, h, a, layer_p, cfg, policy=policy)
        return x, {"k": ck, "v": cv}

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, cache = jax.lax.scan(body, x, (params["layers"],
                                      cache["k"], cache["v"]),
                            unroll=cfg.n_layers if cfg.unroll_scans else 1)
    if all_lanes:
        return _chunk_all_logits(params, cfg, x), cache
    return _chunk_logits(params, cfg, x, clens), cache


@hot_path
def decode_step(params, cfg, token, cache, pos, *, policy=None, live=None):
    """One decode step. token: (B, 1) int32; pos: scalar int32 or per-slot
    (B,) int32 (position of each row's token — the serving engine's slots
    advance independently); cache: stacked KV. Returns (logits,
    new_cache).

    ``live`` (B,) int32, serving only: rows with ``live == 0`` (free slots
    and slots mid-chunk-prefill) must not mutate their cache rows — their
    write position is parked at a droppable sentinel. Their (garbage)
    logits are discarded by the engine as before.

    The layer scan carries the stacked pool whole: each layer writes its
    token rows into the pool in place and flash-decode reads the layer
    where it lies, by index. A scan over the pool's layers as ``xs`` would
    slice each layer out, write it back into a second stacked buffer and
    copy that into the donated output on every step."""
    x = embed_inputs(params, cfg, token)
    dt = _cdtype(cfg)
    # Windowed caches are sized `window`; write position wraps.
    wpos = (pos % cfg.sliding_window) if cfg.sliding_window else pos
    drop = live is not None
    if drop:
        # Park AFTER the ring wrap: a post-modulo position is always in
        # range, so masking before the wrap would alias back into the ring.
        b = token.shape[0]
        wpos = jnp.where(jnp.asarray(live).reshape(-1) > 0,
                         jnp.broadcast_to(
                             jnp.asarray(wpos, jnp.int32).reshape(-1), (b,)),
                         PARKED_POS)

    def body(carry, inp):
        x, ck, cv = carry
        layer_p, l = inp
        layer_p = jax.tree.map(lambda a: a.astype(dt)
                               if a.dtype == jnp.float32 and a.ndim > 1
                               else a, layer_p)
        if cfg.sliding_window:
            # ring buffer: write at wpos; effective length = min(pos+1, W).
            k, v, q = _qkv_single(x, layer_p, cfg, pos)
            if cfg.kv_cache_layout == "bhsd":
                k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
            with jax.named_scope("kv_write"):
                ck = _write_token_kv(ck, k, wpos, cfg.kv_cache_layout,
                                     oob_drop=drop, layer=l)
                cv = _write_token_kv(cv, v, wpos, cfg.kv_cache_layout,
                                     oob_drop=drop, layer=l)
            h = norm_apply(x, layer_p["ln_attn"], cfg.norm, cfg.norm_eps)
            with jax.named_scope("attn"):
                y, _ = _decode_windowed(h, layer_p, cfg, ck, cv, pos, wpos,
                                        policy=policy, layer=l)
            with jax.named_scope("mlp"):
                x = _finish_block(x, h, y, layer_p, cfg, policy=policy)
            return (x, ck, cv), None
        h = norm_apply(x, layer_p["ln_attn"], cfg.norm, cfg.norm_eps)
        # attn_decode scopes its cache write ("kv_write") and its
        # attention ("attn")
        a, (ck, cv) = attn_decode(h, layer_p["attn"], cfg, ck, cv, pos,
                                  policy=policy, write_pos=wpos,
                                  oob_drop=drop, layer=l)
        with jax.named_scope("mlp"):
            x = _finish_block(x, h, a, layer_p, cfg, policy=policy)
        return (x, ck, cv), None

    (x, k, v), _ = jax.lax.scan(
        body, (x, cache["k"], cache["v"]),
        (params["layers"], jnp.arange(cfg.n_layers, dtype=jnp.int32)),
        unroll=cfg.n_layers if cfg.unroll_scans else 1)
    return _final_logits(params, cfg, x), {"k": k, "v": v}


def _final_logits(params, cfg, x):
    x = norm_apply(x, params["ln_f"], cfg.norm, cfg.norm_eps)
    ldt = jnp.bfloat16 if cfg.logits_mm_dtype == "bf16" else jnp.float32
    logits = jnp.einsum("bsd,dv->bsv", x.astype(ldt),
                        unembed_matrix(params, cfg).astype(ldt),
                        preferred_element_type=jnp.float32)
    return mask_padded_logits(logits, cfg.vocab)


@hot_path
def decode_step_sharded(params, cfg, token, cache, pos, *, policy, seq_axis,
                        live=None):
    """One decode step over a sequence-sharded KV cache — the body the
    serving engine wraps in ``shard_map`` (params/token/pos replicated,
    cache sharded along its S axis over ``seq_axis``).

    Per layer: the token's K/V land on exactly the shard owning position
    ``pos`` (drop-mode scatter at local coordinates), each shard sweeps
    its slice in partial-statistics mode, and the statistics fold through
    ``policy.merge_strategy`` — with "packed" that is ONE collective per
    layer; everything outside attention is replicated compute. Windowed
    (ring-buffer) archs keep the GSPMD path: the wrap-around write
    straddles shard boundaries.
    """
    if cfg.sliding_window:
        raise NotImplementedError(
            "sequence-sharded decode covers linear caches; windowed "
            "ring-buffer caches decode through the GSPMD path")
    x = embed_inputs(params, cfg, token)
    dt = _cdtype(cfg)
    wpos = None
    if live is not None:
        # Dead / mid-chunk-prefill rows: every shard sees a parked global
        # position, localizes it out of its slice, and drops the write.
        b = token.shape[0]
        wpos = jnp.where(jnp.asarray(live).reshape(-1) > 0,
                         jnp.broadcast_to(
                             jnp.asarray(pos, jnp.int32).reshape(-1), (b,)),
                         PARKED_POS)

    def body(x, inp):
        layer_p, ck, cv = inp
        layer_p = jax.tree.map(lambda a: a.astype(dt)
                               if a.dtype == jnp.float32 and a.ndim > 1
                               else a, layer_p)
        h = norm_apply(x, layer_p["ln_attn"], cfg.norm, cfg.norm_eps)
        a, (ck, cv) = attn_decode_sharded(h, layer_p["attn"], cfg, ck, cv,
                                          pos, seq_axis=seq_axis,
                                          policy=policy, write_pos=wpos)
        x = _finish_block(x, h, a, layer_p, cfg, policy=policy)
        return x, {"k": ck, "v": cv}

    x, cache = jax.lax.scan(body, x, (params["layers"],
                                      cache["k"], cache["v"]),
                            unroll=cfg.n_layers if cfg.unroll_scans else 1)
    return _final_logits(params, cfg, x), cache


def _qkv_single(x, layer_p, cfg, pos):
    h = norm_apply(x, layer_p["ln_attn"], cfg.norm, cfg.norm_eps)
    b = x.shape[0]
    q, k, v = _qkv(h, layer_p["attn"], cfg, _rope_pos(b, pos))
    return k.astype(jnp.bfloat16), v.astype(jnp.bfloat16), q


def _decode_windowed(h, layer_p, cfg, ck, cv, pos, wpos, *, policy=None,
                     layer=None):
    """Windowed ring-buffer decode: all cache slots valid once pos >= W.
    With ``layer`` the caches are the stacked pool, read at that layer."""
    b = h.shape[0]
    q, _, _ = _qkv(h, layer_p["attn"], cfg, _rope_pos(b, pos))
    w = cfg.sliding_window
    valid = jnp.minimum(pos + 1, w)
    o = decode_attention(q, ck, cv, cache_len=valid, exp_impl=cfg.exp_impl,
                         mm_dtype=cfg.attn_mm_dtype,
                         layout=cfg.kv_cache_layout, policy=policy,
                         layer=layer)
    return o.reshape(b, 1, -1) @ layer_p["attn"]["wo"], None


# ------------------------------------------------------------- paged decode

def init_paged_cache(cfg, n_pages, page, dtype=jnp.bfloat16):
    """Paged KV pool: (L, N, page, Hkv, hd) ("bshd") / (L, N, Hkv, page, hd)
    ("bhsd") ×2. Unlike ``init_cache`` there is no slot axis — physical
    pages are handed to slots by the host-side ``BlockAllocator`` through
    per-slot block tables; page 0 is the reserved scratch page every
    unassigned table entry points at."""
    if cfg.kv_cache_layout == "bhsd":
        shape = (cfg.n_layers, n_pages, cfg.n_kv_heads, page, cfg.hd)
    else:
        shape = (cfg.n_layers, n_pages, page, cfg.n_kv_heads, cfg.hd)
    return {"k": jnp.zeros(shape, dtype), "v": jnp.zeros(shape, dtype)}


def _write_token_kv_paged(pool, kv, gids, offs, layout, *, oob_drop=False):
    """Scatter one token's K (or V) per slot into its physical page.

    pool: (N, page, Hkv, hd) "bshd" / (N, Hkv, page, hd) "bhsd"; kv as in
    ``_write_token_kv``; ``gids``/``offs`` (B,) physical page id and
    in-page offset per slot. Dead slots point at the reserved scratch
    page — their writes collide there harmlessly (scratch is never part
    of any live sweep's masked-in range). ``oob_drop``: the sharded path
    remaps non-owned rows to gid == N, a genuinely droppable index.

    The (page, offset) coordinates are flattened to one row index into a
    reshaped pool: a single-index-array scatter vectorizes on CPU/XLA
    where the equivalent multi-array advanced-index scatter scalarizes
    (~2x the decode-step overhead of the whole indirection)."""
    kv = kv.astype(pool.dtype)
    kw = {"mode": "drop"} if oob_drop else {}
    if layout == "bhsd":
        n, hkv, page, hd = pool.shape
        idx = (gids[:, None] * hkv + jnp.arange(hkv)[None, :]) * page \
            + offs[:, None]
        flat = pool.reshape(n * hkv * page, hd)
        return flat.at[idx].set(kv[:, :, 0], **kw).reshape(pool.shape)
    n, page = pool.shape[0], pool.shape[1]
    flat = pool.reshape((n * page,) + pool.shape[2:])
    return flat.at[gids * page + offs].set(kv[:, 0], **kw).reshape(pool.shape)


def _paged_attn(q, pool_k, pool_v, tab, cache_len, cfg, policy, lay=None):
    """Policy-routed paged sweep: pallas drives the page DMA from the
    table inside the kernel; reference/xla (and the policy-less legacy
    path) gather the table into a contiguous cache first — identical
    semantics, the oracle the kernel is tested against. ``lay`` overrides
    ``cfg.kv_cache_layout`` (the hybrid family's pools are always
    "bshd")."""
    lay = lay or cfg.kv_cache_layout
    if policy is not None:
        from repro.kernels.dispatch import dispatch as k_dispatch
        return k_dispatch("decode_attention_paged", policy)(
            q, pool_k, pool_v, tab, cache_len, window=None, sm_scale=None,
            layout=lay, policy=policy)
    from repro.kernels.decode_attention.ops import paged_gather
    k = paged_gather(pool_k, tab, lay)
    v = paged_gather(pool_v, tab, lay)
    return decode_attention(q, k, v, cache_len=cache_len,
                            exp_impl=cfg.exp_impl,
                            mm_dtype=cfg.attn_mm_dtype, layout=lay)


@hot_path
def decode_step_paged(params, cfg, token, cache, tables, pos, *, policy=None,
                      live=None):
    """One decode step over a paged KV pool. token: (B, 1) int32; cache:
    stacked pools from ``init_paged_cache``; ``tables`` (B, nS) int32
    block table shared by every layer (each layer's pool is indexed by
    the same logical->physical map); pos: per-slot (B,) int32. Returns
    (logits, new_cache) — tables are read-only here; the host allocator
    updates them only at scheduling events.

    Windowed archs run ring-buffer paging: each slot owns a fixed table
    of W/page pages, the write column wraps at W and validity is by
    length only — same semantics as ``decode_step``'s ring cache."""
    x = embed_inputs(params, cfg, token)
    b = x.shape[0]
    dt = _cdtype(cfg)
    lay = cfg.kv_cache_layout
    page = cache["k"].shape[3 if lay == "bhsd" else 2]
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    if cfg.sliding_window:
        w = cfg.sliding_window
        wpos, clen = pos % w, jnp.minimum(pos + 1, w)
    else:
        wpos, clen = pos, pos + 1
    gids = tables[jnp.arange(b), wpos // page]
    offs = wpos % page
    drop = live is not None
    if drop:
        # Dead / mid-chunk-prefill rows write to gid == N — droppable.
        gids = jnp.where(jnp.asarray(live).reshape(-1) > 0, gids,
                         cache["k"].shape[1])

    def body(x, inp):
        layer_p, pk, pv = inp
        layer_p = jax.tree.map(lambda a: a.astype(dt)
                               if a.dtype == jnp.float32 and a.ndim > 1
                               else a, layer_p)
        h = norm_apply(x, layer_p["ln_attn"], cfg.norm, cfg.norm_eps)
        q, k, v = _qkv(h, layer_p["attn"], cfg, _rope_pos(b, pos))
        if lay == "bhsd":
            k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        pk = _write_token_kv_paged(pk, k, gids, offs, lay, oob_drop=drop)
        pv = _write_token_kv_paged(pv, v, gids, offs, lay, oob_drop=drop)
        o = _paged_attn(q, pk, pv, tables, clen, cfg, policy)
        a = o.reshape(b, 1, -1) @ layer_p["attn"]["wo"]
        x = _finish_block(x, h, a, layer_p, cfg, policy=policy)
        return x, {"k": pk, "v": pv}

    x, cache = jax.lax.scan(body, x, (params["layers"],
                                      cache["k"], cache["v"]),
                            unroll=cfg.n_layers if cfg.unroll_scans else 1)
    return _final_logits(params, cfg, x), cache


@hot_path
def decode_step_paged_sharded(params, cfg, token, cache, tables, pos, *,
                              policy, seq_axis, live=None):
    """Paged decode over a sequence-sharded pool — the body the serving
    engine wraps in ``shard_map``. The pool's page axis is sharded over
    ``seq_axis``; ``tables`` is each shard's (B, nS_local) slice holding
    *local* page ids (logical page column j lives on shard j // nS_local
    by the allocator's partitioning). The token's K/V land on exactly the
    owning shard (drop-mode page scatter), each shard sweeps its local
    pages in partial-statistics mode and the statistics fold through
    ``policy.merge_strategy`` — one collective per layer when packed."""
    if cfg.sliding_window:
        raise NotImplementedError(
            "sequence-sharded paged decode covers linear caches; windowed "
            "ring tables decode through the unsharded paged path")
    x = embed_inputs(params, cfg, token)
    b = x.shape[0]
    dt = _cdtype(cfg)
    lay = cfg.kv_cache_layout
    page = cache["k"].shape[3 if lay == "bhsd" else 2]
    n_local = cache["k"].shape[1]
    ns_local = tables.shape[1]
    s_local = ns_local * page
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    off = jax.lax.axis_index(seq_axis) * s_local
    lp = pos - off
    own = (lp >= 0) & (lp < s_local)
    if live is not None:
        own &= jnp.asarray(live).reshape(-1) > 0
    lpc = jnp.clip(lp, 0, s_local - 1)
    gids = jnp.where(own, tables[jnp.arange(b), lpc // page], n_local)
    offs = jnp.where(own, lpc % page, 0)
    from repro.kernels.decode_attention.ops import \
        decode_attention_paged_partial_merged

    def body(x, inp):
        layer_p, pk, pv = inp
        layer_p = jax.tree.map(lambda a: a.astype(dt)
                               if a.dtype == jnp.float32 and a.ndim > 1
                               else a, layer_p)
        h = norm_apply(x, layer_p["ln_attn"], cfg.norm, cfg.norm_eps)
        q, k, v = _qkv(h, layer_p["attn"], cfg, _rope_pos(b, pos))
        if lay == "bhsd":
            k, v = k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3)
        pk = _write_token_kv_paged(pk, k, gids, offs, lay, oob_drop=True)
        pv = _write_token_kv_paged(pv, v, gids, offs, lay, oob_drop=True)
        o = decode_attention_paged_partial_merged(
            q, pk, pv, tables, pos + 1, off, seq_axis=seq_axis, layout=lay,
            policy=policy)
        a = o.reshape(b, 1, -1) @ layer_p["attn"]["wo"]
        x = _finish_block(x, h, a, layer_p, cfg, policy=policy)
        return x, {"k": pk, "v": pv}

    x, cache = jax.lax.scan(body, x, (params["layers"],
                                      cache["k"], cache["v"]),
                            unroll=cfg.n_layers if cfg.unroll_scans else 1)
    return _final_logits(params, cfg, x), cache


def _write_chunk_kv_paged(pool, kv, gids, inpage, layout):
    """Scatter a C-token chunk into physical pages.

    kv: (B, C, Hkv, hd); gids/inpage: (B, C) physical page id and in-page
    offset per token, with invalid lanes pre-remapped to gid == N
    (droppable). Same flattened single-index scatter as the decode-step
    write."""
    kv = kv.astype(pool.dtype)
    kw = {"mode": "drop"}
    if layout == "bhsd":
        n, hkv, page, hd = pool.shape
        idx = (gids[:, :, None] * hkv
               + jnp.arange(hkv)[None, None, :]) * page + inpage[:, :, None]
        flat = pool.reshape(n * hkv * page, hd)
        return flat.at[idx].set(kv, **kw).reshape(pool.shape)
    n, page = pool.shape[0], pool.shape[1]
    flat = pool.reshape((n * page,) + pool.shape[2:])
    return flat.at[gids * page + inpage].set(kv, **kw).reshape(pool.shape)


def prefill_chunk_paged(params, cfg, tokens, cache, tables, off, clens, *,
                        policy=None, all_lanes=False):
    """Resumable prefill over a paged KV pool: the chunk's K/V scatter
    into each slot's reserved pages at its cursor, then the Q-chunk
    attends causally over the slot's gathered pages — shared-prefix pages
    (attached read-only at admission; the cursor starts past them) and
    intra-chunk keys included. Linear caches only; windowed ring tables
    admit monolithically. Arguments as ``prefill_chunk`` plus ``tables``
    (B, nS) physical page tables. Returns (logits, cache);
    ``all_lanes=True`` (speculative verify) returns every lane's
    logits."""
    from repro.kernels.decode_attention.ops import paged_gather
    x = embed_inputs(params, cfg, tokens)
    b, c, _ = x.shape
    off = jnp.asarray(off, jnp.int32).reshape(-1)
    clens = jnp.asarray(clens, jnp.int32).reshape(-1)
    dt = _cdtype(cfg)
    lay = cfg.kv_cache_layout
    page = cache["k"].shape[3 if lay == "bhsd" else 2]
    n = cache["k"].shape[1]
    ns = tables.shape[1]
    pos = off[:, None] + jnp.arange(c)[None, :]            # (B, C)
    lane = jnp.arange(c)[None, :] < clens[:, None]
    cols = jnp.clip(pos // page, 0, ns - 1)
    gids = jnp.where(lane, tables[jnp.arange(b)[:, None], cols], n)
    inpage = jnp.where(lane, pos % page, 0)
    kv_valid = (jnp.arange(ns * page)[None, :]
                < (off + clens)[:, None])                  # (B, nS*page)

    def body(x, inp):
        layer_p, pk, pv = inp
        layer_p = jax.tree.map(lambda a: a.astype(dt)
                               if a.dtype == jnp.float32 and a.ndim > 1
                               else a, layer_p)
        h = norm_apply(x, layer_p["ln_attn"], cfg.norm, cfg.norm_eps)
        q, k, v = _qkv(h, layer_p["attn"], cfg, pos)
        k = jnp.where(lane[:, :, None, None], k, 0)
        v = jnp.where(lane[:, :, None, None], v, 0)
        pk = _write_chunk_kv_paged(pk, k, gids, inpage, lay)
        pv = _write_chunk_kv_paged(pv, v, gids, inpage, lay)
        kk = paged_gather(pk, tables, lay)
        vv = paged_gather(pv, tables, lay)
        if lay == "bhsd":
            kk, vv = kk.transpose(0, 2, 1, 3), vv.transpose(0, 2, 1, 3)
        o = attention(q, kk, vv, causal=True, window=None, q_offset=off,
                      exp_impl=cfg.exp_impl, impl=cfg.attention_impl,
                      unroll=cfg.unroll_scans, block_k=cfg.attn_block_k,
                      mm_dtype=cfg.attn_mm_dtype, kv_valid=kv_valid,
                      policy=masked_policy(policy))
        a = o.reshape(b, c, -1) @ layer_p["attn"]["wo"]
        x = _finish_block(x, h, a, layer_p, cfg, policy=policy)
        return x, {"k": pk, "v": pv}

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, cache = jax.lax.scan(body, x, (params["layers"],
                                      cache["k"], cache["v"]),
                            unroll=cfg.n_layers if cfg.unroll_scans else 1)
    if all_lanes:
        return _chunk_all_logits(params, cfg, x), cache
    return _chunk_logits(params, cfg, x, clens), cache


def _finish_block(x, h, a, layer_p, cfg, *, policy=None):
    if cfg.parallel_block:
        if cfg.n_experts:
            m, _ = moe_apply(h, layer_p["moe"], cfg)
        else:
            m = mlp_apply(h, layer_p["mlp"], cfg.act, cfg.exp_impl,
                          policy=policy)
        return x + a + m
    x = x + a
    h2 = norm_apply(x, layer_p["ln_mlp"], cfg.norm, cfg.norm_eps)
    if cfg.n_experts:
        m, _ = moe_apply(h2, layer_p["moe"], cfg)
    else:
        m = mlp_apply(h2, layer_p["mlp"], cfg.act, cfg.exp_impl,
                      policy=policy)
    return x + m
