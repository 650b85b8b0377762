"""RecurrentGemma / Griffin hybrid: RG-LRU recurrent blocks + local attention.

Layer pattern is 1 local-attention layer per ``cfg.attn_period`` (= 3 for
recurrentgemma: rec, rec, attn), scanned over whole periods with the tail
(n_layers % period, recurrent) handled explicitly.

Arch-applicability (DESIGN.md §4): the paper's softmax kernel applies to the
local-attention layers and final logits; the RG-LRU gates are
sigmoid/softplus — also exponential-family, computed via the same VEXP
primitive:  a_t = exp(c · r_t · log a)  is literally a vexp call on a
non-positive argument (vexp's best-accuracy range).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.kernels.dispatch import exp_callable
from .layers import (dense_init, embed_init, norm_init, norm_apply,
                     vexp_sigmoid, gelu, mlp_init, mlp_apply, cross_entropy,
                     mask_padded_logits)
from .state_spec import LeafAxes
from .transformer import (attn_init, attn_apply, attn_decode, _qkv,
                          _rope_pos, _write_token_kv, _write_chunk_kv,
                          _write_chunk_kv_paged, PARKED_POS)

RG_LRU_C = 8.0     # Griffin's fixed exponent scale


# ------------------------------------------------------------ RG-LRU block

def rec_layer_init(key, cfg, dtype=jnp.float32):
    d, w = cfg.d_model, cfg.lru_width or cfg.d_model
    ks = jax.random.split(key, 8)
    # Lambda init so that a = sigmoid(lam) in [0.9, 0.999] (Griffin app. A)
    u = jax.random.uniform(ks[6], (w,), jnp.float32, 0.9, 0.999)
    lam = jnp.log(u ** 2 / (1 - u ** 2))
    return {
        "ln": norm_init(d, cfg.norm),
        "wx": dense_init(ks[0], d, w, dtype),          # recurrent branch
        "wy": dense_init(ks[1], d, w, dtype),          # gate branch
        "conv_w": (jax.random.normal(ks[2], (cfg.conv_width, w),
                                     jnp.float32) * 0.1).astype(dtype),
        "conv_b": jnp.zeros((w,), dtype),
        "w_input_gate": dense_init(ks[3], w, w, dtype),
        "w_rec_gate": dense_init(ks[4], w, w, dtype),
        "lam": lam,
        "w_out": dense_init(ks[5], w, d, dtype),
        "ln_mlp": norm_init(d, cfg.norm),
        "mlp": mlp_init(ks[7], d, cfg.d_ff, cfg.act, cfg.use_bias, dtype),
    }


def _rg_lru(xw, p, cfg, h0=None, last_idx=None, policy=None):
    """RG-LRU over a sequence. xw: (B, S, W). Returns (y, h_last).

    h_t = a_t * h_{t-1} + sqrt(1 - a_t^2) * (i_t * x_t),
    log a_t = -c * r_t * softplus(-lam)  (= c*r_t*log sigmoid(lam) <= 0).
    Parallelized with an associative scan in the log-decay domain.

    ``last_idx`` (B,) gathers each row's state at that position instead of
    the sequence end (ragged right-padded prefill: the state at the last
    *real* token — a prefix-scan element depends only on positions <= it,
    so no masking of the padded tail is needed).
    """
    exp_fn = exp_callable(policy, cfg.exp_impl)
    xf = xw.astype(jnp.float32)
    r = vexp_sigmoid(xf @ p["w_rec_gate"].astype(jnp.float32), exp_fn)
    i = vexp_sigmoid(xf @ p["w_input_gate"].astype(jnp.float32), exp_fn)
    log_a_base = -jnp.logaddexp(0.0, -p["lam"])       # log sigmoid(lam) <= 0
    log_a = RG_LRU_C * r * log_a_base                 # (B, S, W)
    a = exp_fn(log_a)
    b = jnp.sqrt(jnp.maximum(1.0 - exp_fn(2.0 * log_a), 0.0)) * (i * xf)

    # associative scan over seq: elements (log_a, b); an initial state h0
    # contributes prod(a_{1..t}) * h0, added after the scan.
    def combine(e1, e2):
        la1, b1 = e1
        la2, b2 = e2
        return la1 + la2, exp_fn(la2) * b1 + b2

    la_acc, h = jax.lax.associative_scan(combine, (log_a, b), axis=1)
    if h0 is not None:
        h = h + exp_fn(la_acc) * h0[:, None, :]
    if last_idx is None:
        h_last = h[:, -1]
    else:
        h_last = jnp.take_along_axis(
            h, jnp.asarray(last_idx, jnp.int32).reshape(-1, 1, 1), axis=1
        )[:, 0]
    return h.astype(xw.dtype), h_last


def rec_layer_apply(x, p, cfg, h0=None, conv_state=None, last_idx=None,
                    valid_len=None, policy=None):
    """Full-sequence recurrent block. Returns (y, (h_last, conv_state)).

    ``last_idx``/``valid_len`` (both (B,), = prompt_len - 1 / prompt_len)
    take each row's recurrent and conv state at its last real token."""
    hin = norm_apply(x, p["ln"], cfg.norm, cfg.norm_eps)
    u = hin @ p["wx"]
    # temporal conv (depthwise, causal)
    from .ssm import _causal_conv
    u, conv_state = _causal_conv(u, p["conv_w"], p["conv_b"], conv_state,
                                 valid_len=valid_len)
    y, h_last = _rg_lru(u, p, cfg, h0, last_idx=last_idx, policy=policy)
    gate = gelu(hin @ p["wy"])
    out = (y * gate) @ p["w_out"]
    x = x + out
    h2 = norm_apply(x, p["ln_mlp"], cfg.norm, cfg.norm_eps)
    x = x + mlp_apply(h2, p["mlp"], cfg.act, cfg.exp_impl)
    return x, (h_last, conv_state)


def rec_layer_decode(x, p, cfg, state, policy=None):
    """Single-token decode. state: {"h": (B, W), "conv": (B, W-1, W)}."""
    exp_fn = exp_callable(policy, cfg.exp_impl)
    hin = norm_apply(x, p["ln"], cfg.norm, cfg.norm_eps)
    u = hin @ p["wx"]
    from .ssm import _causal_conv
    u, new_conv = _causal_conv(u, p["conv_w"], p["conv_b"], state["conv"])
    uf = u[:, 0].astype(jnp.float32)
    r = vexp_sigmoid(uf @ p["w_rec_gate"].astype(jnp.float32), exp_fn)
    i = vexp_sigmoid(uf @ p["w_input_gate"].astype(jnp.float32), exp_fn)
    log_a_base = -jnp.logaddexp(0.0, -p["lam"])
    log_a = RG_LRU_C * r * log_a_base
    a = exp_fn(log_a)
    bterm = jnp.sqrt(jnp.maximum(1.0 - exp_fn(2 * log_a), 0.0)) * (i * uf)
    h = a * state["h"] + bterm
    gate = gelu(hin[:, 0] @ p["wy"])
    out = ((h.astype(x.dtype) * gate) @ p["w_out"])[:, None, :]
    x = x + out
    h2 = norm_apply(x, p["ln_mlp"], cfg.norm, cfg.norm_eps)
    x = x + mlp_apply(h2, p["mlp"], cfg.act, cfg.exp_impl)
    return x, {"h": h, "conv": new_conv}


# ----------------------------------------------------- attention sub-block

def attn_layer_init(key, cfg, dtype=jnp.float32):
    ks = jax.random.split(key, 2)
    return {"ln": norm_init(cfg.d_model, cfg.norm),
            "attn": attn_init(ks[0], cfg, dtype),
            "ln_mlp": norm_init(cfg.d_model, cfg.norm),
            "mlp": mlp_init(ks[1], cfg.d_model, cfg.d_ff, cfg.act,
                            cfg.use_bias, dtype)}


def attn_layer_apply(x, p, cfg, pos, kv_valid=None, policy=None):
    h = norm_apply(x, p["ln"], cfg.norm, cfg.norm_eps)
    a, kv = attn_apply(h, p["attn"], cfg, pos, window=cfg.sliding_window,
                       kv_valid=kv_valid, policy=policy)
    x = x + a
    h2 = norm_apply(x, p["ln_mlp"], cfg.norm, cfg.norm_eps)
    x = x + mlp_apply(h2, p["mlp"], cfg.act, cfg.exp_impl)
    return x, kv


def attn_layer_decode(x, p, cfg, ck, cv, pos, wpos, policy=None,
                      oob_drop=False):
    """Single-token local-attention decode. ``pos`` (and the ring-buffer
    write cursor ``wpos``) may be a scalar or a per-slot (B,) vector — the
    continuous-batching engine's slots each advance at their own
    position; the scatter write and the per-row cache_len mask keep them
    independent. ``oob_drop`` lets parked rows (wpos == PARKED_POS) skip
    their cache write entirely."""
    from repro.core.attention import decode_attention
    b = x.shape[0]
    h = norm_apply(x, p["ln"], cfg.norm, cfg.norm_eps)
    q, k, v = _qkv(h, p["attn"], cfg, _rope_pos(b, pos))
    ck = _write_token_kv(ck, k, wpos, "bshd", oob_drop=oob_drop)
    cv = _write_token_kv(cv, v, wpos, "bshd", oob_drop=oob_drop)
    w = cfg.sliding_window
    pos = jnp.asarray(pos, jnp.int32)
    valid = jnp.minimum(pos + 1, w) if w else pos + 1
    o = decode_attention(q, ck, cv, cache_len=valid, exp_impl=cfg.exp_impl,
                         mm_dtype=cfg.attn_mm_dtype, policy=policy)
    x = x + o.reshape(b, 1, -1) @ p["attn"]["wo"]
    h2 = norm_apply(x, p["ln_mlp"], cfg.norm, cfg.norm_eps)
    x = x + mlp_apply(h2, p["mlp"], cfg.act, cfg.exp_impl)
    return x, ck, cv


# ---------------------------------------------------------------- full model

def _period_counts(cfg):
    period = cfg.attn_period
    n_per = cfg.n_layers // period            # scanned periods
    tail = cfg.n_layers % period              # trailing recurrent layers
    return period, n_per, tail


def init_params(cfg, key):
    period, n_per, tail = _period_counts(cfg)
    n_rec_per = period - 1
    ks = jax.random.split(key, n_per + tail + 3)
    periods = []
    for i in range(n_per):
        sub = jax.random.split(ks[i], period)
        periods.append({
            "recs": jax.tree.map(
                lambda *xs: jnp.stack(xs),
                *[rec_layer_init(sub[j], cfg) for j in range(n_rec_per)]),
            "attn": attn_layer_init(sub[-1], cfg),
        })
    stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *periods)
    p = {"periods": stacked,
         "ln_f": norm_init(cfg.d_model, cfg.norm),
         "embed": embed_init(ks[-1], cfg.vocab_padded, cfg.d_model),
         "unembed": dense_init(ks[-2], cfg.d_model, cfg.vocab_padded)}
    if tail:
        p["tail"] = jax.tree.map(
            lambda *xs: jnp.stack(xs),
            *[rec_layer_init(ks[n_per + j], cfg) for j in range(tail)])
    return p


def _cast(layer_p, dt):
    return jax.tree.map(lambda a: a.astype(dt)
                        if a.dtype == jnp.float32 and a.ndim > 1 else a,
                        layer_p)


def forward(params, cfg, tokens, *, policy=None):
    dt = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    b, s = tokens.shape
    pos = jnp.arange(s)[None, :].astype(jnp.int32)
    period, n_per, tail = _period_counts(cfg)

    def body(x, period_p):
        period_p = _cast(period_p, dt)

        def rec_body(x, rec_p):
            y, _ = rec_layer_apply(x, rec_p, cfg, policy=policy)
            return y, None

        x, _ = jax.lax.scan(rec_body, x, period_p["recs"],
                            unroll=cfg.unroll_scans)
        x, _ = attn_layer_apply(x, period_p["attn"], cfg, pos,
                                policy=policy)
        return x, None

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    n_per = cfg.n_layers // cfg.attn_period
    x, _ = jax.lax.scan(body, x, params["periods"],
                        unroll=n_per if cfg.unroll_scans else 1)
    if tail:
        def tail_body(x, rec_p):
            y, _ = rec_layer_apply(x, rec_p, cfg, policy=policy)
            return y, None
        x, _ = jax.lax.scan(tail_body, x, _cast(params["tail"], dt),
                            unroll=cfg.unroll_scans)
    return norm_apply(x, params["ln_f"], cfg.norm, cfg.norm_eps)


def loss_fn(params, cfg, batch, *, policy=None):
    x = forward(params, cfg, batch["tokens"], policy=policy)
    return cross_entropy(x, params["unembed"], batch["labels"],
                         chunk=cfg.loss_chunk, exp_impl=cfg.exp_impl,
                         mask=batch.get("mask"), unroll=cfg.unroll_scans)


def init_cache(cfg, batch, seq_len, dtype=jnp.bfloat16):
    period, n_per, tail = _period_counts(cfg)
    w = cfg.lru_width or cfg.d_model
    win = min(seq_len, cfg.sliding_window or seq_len)
    cache = {"periods": {
        "rec_h": jnp.zeros((n_per, period - 1, batch, w), jnp.float32),
        "rec_conv": jnp.zeros((n_per, period - 1, batch,
                               cfg.conv_width - 1, w), jnp.float32),
        "k": jnp.zeros((n_per, batch, win, cfg.n_kv_heads, cfg.hd), dtype),
        "v": jnp.zeros((n_per, batch, win, cfg.n_kv_heads, cfg.hd), dtype),
    }}
    if tail:
        cache["tail"] = {
            "h": jnp.zeros((tail, batch, w), jnp.float32),
            "conv": jnp.zeros((tail, batch, cfg.conv_width - 1, w),
                              jnp.float32)}
    return cache


def cache_axes(cfg):
    """DecodeState leaf metadata for the mixed per-period state: the
    recurrent snapshots carry only a slot axis; the local-attention KV
    leaves additionally have a sequence axis (ring-buffer window)."""
    period, n_per, tail = _period_counts(cfg)
    axes = {"periods": {"rec_h": LeafAxes(2), "rec_conv": LeafAxes(2),
                        "k": LeafAxes(1, 2), "v": LeafAxes(1, 2)}}
    if tail:
        axes["tail"] = {"h": LeafAxes(1), "conv": LeafAxes(1)}
    return axes


def prefill(params, cfg, tokens, *, prompt_len=None, policy=None):
    """Prompt forward -> (last_logits, cache).

    ``prompt_len`` (B,) marks ragged right-padded prompts: padding is
    masked out of the local attention (and its pad K/V rows zeroed), each
    recurrent layer's (h, conv) state is gathered at the row's last real
    token, and so are the returned logits. Ragged batches must fit the
    sliding window (the ring-buffer roll is batch-uniform)."""
    dt = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    b, s = tokens.shape
    pos = jnp.arange(s)[None, :].astype(jnp.int32)
    period, n_per, tail = _period_counts(cfg)
    win = min(s, cfg.sliding_window or s)
    plen = kv_valid = last_idx = None
    if prompt_len is not None:
        if cfg.sliding_window and s > cfg.sliding_window:
            raise ValueError(
                f"ragged prefill of {s} tokens exceeds the sliding window "
                f"({cfg.sliding_window}): the ring-buffer roll is batch-"
                f"uniform; prefill ragged windowed batches at <= window")
        plen = jnp.asarray(prompt_len, jnp.int32).reshape(-1)
        kv_valid = jnp.arange(s)[None, :] < plen[:, None]        # (B, S)
        last_idx = jnp.clip(plen - 1, 0, s - 1)

    def body(x, period_p):
        period_p = _cast(period_p, dt)

        def rec_body(x, rec_p):
            y, (h, conv) = rec_layer_apply(x, rec_p, cfg, last_idx=last_idx,
                                           valid_len=plen, policy=policy)
            return y, (h, conv.astype(jnp.float32))

        x, (hs, convs) = jax.lax.scan(rec_body, x, period_p["recs"],
                                      unroll=cfg.unroll_scans)
        x, (k, v) = attn_layer_apply(x, period_p["attn"], cfg, pos,
                                     kv_valid=kv_valid, policy=policy)
        if kv_valid is not None:
            # pad rows must not reach the decode cache (freed-slot hygiene)
            k = jnp.where(kv_valid[:, :, None, None], k, 0)
            v = jnp.where(kv_valid[:, :, None, None], v, 0)
        k, v = k[:, -win:], v[:, -win:]
        if cfg.sliding_window and s > cfg.sliding_window:
            # ring-buffer layout: slot = absolute position % window
            k = jnp.roll(k, s % cfg.sliding_window, axis=1)
            v = jnp.roll(v, s % cfg.sliding_window, axis=1)
        return x, {"rec_h": hs, "rec_conv": convs,
                   "k": k.astype(jnp.bfloat16), "v": v.astype(jnp.bfloat16)}

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    n_per = cfg.n_layers // cfg.attn_period
    x, pcache = jax.lax.scan(body, x, params["periods"],
                             unroll=n_per if cfg.unroll_scans else 1)
    cache = {"periods": pcache}
    if tail:
        def tail_body(x, rec_p):
            y, (h, conv) = rec_layer_apply(x, rec_p, cfg, last_idx=last_idx,
                                           valid_len=plen, policy=policy)
            return y, {"h": h, "conv": conv.astype(jnp.float32)}
        x, tcache = jax.lax.scan(tail_body, x, _cast(params["tail"], dt),
                                 unroll=cfg.unroll_scans)
        cache["tail"] = tcache
    x = norm_apply(x, params["ln_f"], cfg.norm, cfg.norm_eps)
    if prompt_len is None:
        xl = x[:, -1:]
    else:
        idx = last_idx[:, None, None]
        xl = jnp.take_along_axis(
            x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
    ldt = jnp.bfloat16 if cfg.logits_mm_dtype == "bf16" else jnp.float32
    logits = jnp.einsum("bsd,dv->bsv", xl.astype(ldt),
                        params["unembed"].astype(ldt),
                        preferred_element_type=jnp.float32)
    return mask_padded_logits(logits, cfg.vocab), cache


def _attn_chunk(h, p, cfg, ck, cv, off, clens, policy=None):
    """Chunk-prefill local attention: scatter the chunk's K/V into the
    ring cache at per-row cursor offsets, then attend the Q-chunk
    causally (window-masked) over the updated cache. Prefill positions
    never wrap the ring — prompts fit the window (the same invariant the
    monolithic ragged path enforces) — so cache slot == absolute
    position throughout prefill. Returns (attn_out, ck, cv)."""
    from repro.core.attention import attention, masked_policy
    b, c, _ = h.shape
    s = ck.shape[1]
    pos = off[:, None] + jnp.arange(c)[None, :]            # (B, C)
    q, k, v = _qkv(h, p, cfg, pos)
    lane = jnp.arange(c)[None, :] < clens[:, None]
    k = jnp.where(lane[:, :, None, None], k, 0)            # pad hygiene
    v = jnp.where(lane[:, :, None, None], v, 0)
    rows = jnp.where(lane, pos, s)                         # invalid -> drop
    ck = _write_chunk_kv(ck, k, rows, "bshd")
    cv = _write_chunk_kv(cv, v, rows, "bshd")
    kv_valid = jnp.arange(s)[None, :] < (off + clens)[:, None]
    o = attention(q, ck, cv, causal=True, window=cfg.sliding_window,
                  q_offset=off, exp_impl=cfg.exp_impl,
                  impl=cfg.attention_impl, unroll=cfg.unroll_scans,
                  block_k=cfg.attn_block_k, mm_dtype=cfg.attn_mm_dtype,
                  kv_valid=kv_valid, policy=masked_policy(policy))
    return o.reshape(b, c, -1) @ p["wo"], ck, cv


def _chunk_last_logits(params, cfg, x, last_idx):
    b = x.shape[0]
    x = norm_apply(x, params["ln_f"], cfg.norm, cfg.norm_eps)
    idx = last_idx[:, None, None]
    xl = jnp.take_along_axis(
        x, jnp.broadcast_to(idx, (b, 1, x.shape[-1])), axis=1)
    ldt = jnp.bfloat16 if cfg.logits_mm_dtype == "bf16" else jnp.float32
    logits = jnp.einsum("bsd,dv->bsv", xl.astype(ldt),
                        params["unembed"].astype(ldt),
                        preferred_element_type=jnp.float32)
    return mask_padded_logits(logits, cfg.vocab)


def _prefill_chunk_impl(params, cfg, tokens, cache, off, clens, policy,
                        attn_fn):
    """Shared chunked-prefill driver: recurrent layers continue from the
    carried (h, conv) snapshots; the per-period attention layer is
    supplied by ``attn_fn(x_normed, period_attn_p, kv_leaves) ->
    (attn_out, new_kv_leaves)``. Rows with ``clens == 0`` are inert: the
    RG-LRU keeps its carried state explicitly (``last_idx`` would clamp
    to 0 and take one real recurrence step otherwise), the conv state
    gathers back its own left context, and KV writes park."""
    dt = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], tokens, axis=0).astype(dt)
    b, c = tokens.shape
    clens = jnp.asarray(clens, jnp.int32).reshape(-1)
    period, n_per, tail = _period_counts(cfg)
    last_idx = jnp.clip(clens - 1, 0, c - 1)
    alive = clens > 0

    def rec_chunk(x, rec_p, h, conv):
        y, (h_last, new_conv) = rec_layer_apply(
            x, rec_p, cfg, h0=h, conv_state=conv, last_idx=last_idx,
            valid_len=clens, policy=policy)
        h_last = jnp.where(alive[:, None], h_last, h)
        return y, (h_last, new_conv.astype(jnp.float32))

    def body(x, inp):
        period_p, pc = inp
        period_p = _cast(period_p, dt)

        def rec_body(x, rec_inp):
            rec_p, h, conv = rec_inp
            return rec_chunk(x, rec_p, h, conv)

        x, (hs, convs) = jax.lax.scan(
            rec_body, x, (period_p["recs"], pc["rec_h"], pc["rec_conv"]),
            unroll=cfg.unroll_scans)
        ap = period_p["attn"]
        h = norm_apply(x, ap["ln"], cfg.norm, cfg.norm_eps)
        a, kv = attn_fn(h, ap["attn"], {"k": pc["k"], "v": pc["v"]})
        x = x + a
        h2 = norm_apply(x, ap["ln_mlp"], cfg.norm, cfg.norm_eps)
        x = x + mlp_apply(h2, ap["mlp"], cfg.act, cfg.exp_impl)
        return x, {"rec_h": hs, "rec_conv": convs,
                   "k": kv["k"], "v": kv["v"]}

    if cfg.remat:
        body = jax.checkpoint(body, prevent_cse=False)
    x, pcache = jax.lax.scan(body, x, (params["periods"], cache["periods"]),
                             unroll=n_per if cfg.unroll_scans else 1)
    new_cache = {"periods": pcache}
    if tail:
        def tail_body(x, rec_inp):
            rec_p, h, conv = rec_inp
            y, (h_last, conv2) = rec_chunk(x, rec_p, h, conv)
            return y, {"h": h_last, "conv": conv2}
        x, tcache = jax.lax.scan(
            tail_body, x, (_cast(params["tail"], dt), cache["tail"]["h"],
                           cache["tail"]["conv"]), unroll=cfg.unroll_scans)
        new_cache["tail"] = tcache
    return _chunk_last_logits(params, cfg, x, last_idx), new_cache


def prefill_chunk(params, cfg, tokens, cache, off, clens, *, policy=None):
    """Resumable chunked prefill over the contiguous hybrid cache: each
    recurrent layer continues from its carried (h, conv) snapshot and the
    local-attention layers write/attend the ring KV at per-row cursors.
    The RG-LRU combine tree depends on the scan length, so chunk widths
    must be pinned (scan-length-invariant) for run-to-run determinism;
    chunked output is token-identical — not bitwise — to one-shot prefill
    (whose combine tree spans the full padded width). Arguments and
    semantics as ``transformer.prefill_chunk``."""
    off = jnp.asarray(off, jnp.int32).reshape(-1)
    clens = jnp.asarray(clens, jnp.int32).reshape(-1)

    def attn_fn(h, ap, kv):
        a, ck, cv = _attn_chunk(h, ap, cfg, kv["k"], kv["v"], off, clens,
                                policy=policy)
        return a, {"k": ck, "v": cv}

    return _prefill_chunk_impl(params, cfg, tokens, cache, off, clens,
                               policy, attn_fn)


def prefill_chunk_paged(params, cfg, tokens, cache, tables, off, clens, *,
                        policy=None):
    """Chunked prefill over a paged hybrid cache: recurrent snapshots as
    in ``prefill_chunk``; the chunk's K/V scatter into each slot's ring
    pages at its cursor and the Q-chunk attends the gathered pages.
    Prefill positions never wrap the ring (prompts fit the window), so
    ``tables[b, pos // page]`` is cursor-monotonic during prefill."""
    from repro.kernels.decode_attention.ops import paged_gather
    from repro.core.attention import attention, masked_policy
    b, c = tokens.shape
    off = jnp.asarray(off, jnp.int32).reshape(-1)
    clens = jnp.asarray(clens, jnp.int32).reshape(-1)
    page = cache["periods"]["k"].shape[2]      # (n_per, N, page, Hkv, hd)
    n = cache["periods"]["k"].shape[1]
    ns = tables.shape[1]
    pos = off[:, None] + jnp.arange(c)[None, :]
    lane = jnp.arange(c)[None, :] < clens[:, None]
    cols = jnp.clip(pos // page, 0, ns - 1)
    gids = jnp.where(lane, tables[jnp.arange(b)[:, None], cols], n)
    inpage = jnp.where(lane, pos % page, 0)
    kv_valid = jnp.arange(ns * page)[None, :] < (off + clens)[:, None]

    def attn_fn(h, ap, kv):
        q, k, v = _qkv(h, ap, cfg, pos)
        k = jnp.where(lane[:, :, None, None], k, 0)
        v = jnp.where(lane[:, :, None, None], v, 0)
        pk = _write_chunk_kv_paged(kv["k"], k, gids, inpage, "bshd")
        pv = _write_chunk_kv_paged(kv["v"], v, gids, inpage, "bshd")
        kk = paged_gather(pk, tables, "bshd")
        vv = paged_gather(pv, tables, "bshd")
        o = attention(q, kk, vv, causal=True, window=cfg.sliding_window,
                      q_offset=off, exp_impl=cfg.exp_impl,
                      impl=cfg.attention_impl, unroll=cfg.unroll_scans,
                      block_k=cfg.attn_block_k, mm_dtype=cfg.attn_mm_dtype,
                      kv_valid=kv_valid, policy=masked_policy(policy))
        return o.reshape(h.shape[0], c, -1) @ ap["wo"], {"k": pk, "v": pv}

    return _prefill_chunk_impl(params, cfg, tokens, cache, off, clens,
                               policy, attn_fn)


def init_paged_cache(cfg, batch, n_pages, page, dtype=jnp.bfloat16):
    """Paged hybrid state: the recurrent leaves keep their slot axis
    (O(1) per slot — nothing to page), the local-attention KV leaves
    become slotless page pools (n_per, N, page, Hkv, hd), always "bshd".
    Every period indexes the same per-slot ring block table: each slot
    owns a fixed W/page pages for the life of its request and the write
    column wraps at the window — paging changes where the ring lives,
    not its semantics."""
    period, n_per, tail = _period_counts(cfg)
    w = cfg.lru_width or cfg.d_model
    cache = {"periods": {
        "rec_h": jnp.zeros((n_per, period - 1, batch, w), jnp.float32),
        "rec_conv": jnp.zeros((n_per, period - 1, batch,
                               cfg.conv_width - 1, w), jnp.float32),
        "k": jnp.zeros((n_per, n_pages, page, cfg.n_kv_heads, cfg.hd),
                       dtype),
        "v": jnp.zeros((n_per, n_pages, page, cfg.n_kv_heads, cfg.hd),
                       dtype),
    }}
    if tail:
        cache["tail"] = {
            "h": jnp.zeros((tail, batch, w), jnp.float32),
            "conv": jnp.zeros((tail, batch, cfg.conv_width - 1, w),
                              jnp.float32)}
    return cache


def attn_layer_decode_paged(x, p, cfg, pk, pv, tables, pos, wpos,
                            policy=None, live=None):
    """``attn_layer_decode`` against a page pool: the ring write lands in
    page ``tables[b, wpos // page]`` at offset ``wpos % page``; validity
    stays by-length (the ring holds exactly the window). ``live`` parks
    dead rows' writes at gid == N (droppable) — the write position can't
    be parked directly, a parked ``wpos // page`` would clamp back into
    the table."""
    from .transformer import _paged_attn, _write_token_kv_paged
    b = x.shape[0]
    page = pk.shape[1]
    h = norm_apply(x, p["ln"], cfg.norm, cfg.norm_eps)
    q, k, v = _qkv(h, p["attn"], cfg, _rope_pos(b, pos))
    gids = tables[jnp.arange(b), wpos // page]
    drop = live is not None
    if drop:
        gids = jnp.where(jnp.asarray(live).reshape(-1) > 0, gids,
                         pk.shape[0])
    pk = _write_token_kv_paged(pk, k, gids, wpos % page, "bshd",
                               oob_drop=drop)
    pv = _write_token_kv_paged(pv, v, gids, wpos % page, "bshd",
                               oob_drop=drop)
    w = cfg.sliding_window
    pos = jnp.asarray(pos, jnp.int32)
    valid = jnp.minimum(pos + 1, w) if w else pos + 1
    o = _paged_attn(q, pk, pv, tables, valid, cfg, policy, lay="bshd")
    x = x + o.reshape(b, 1, -1) @ p["attn"]["wo"]
    h2 = norm_apply(x, p["ln_mlp"], cfg.norm, cfg.norm_eps)
    x = x + mlp_apply(h2, p["mlp"], cfg.act, cfg.exp_impl)
    return x, pk, pv


def decode_step_paged(params, cfg, token, cache, tables, pos, *, policy=None,
                      live=None):
    """One decode step over a paged hybrid cache (see init_paged_cache).
    ``tables`` (B, W/page) int32 ring block table shared by every period;
    ``pos`` per-slot (B,) int32. ``live`` (B,) masks dead rows' state
    updates (recurrent snapshots kept, KV writes parked at gid == N)."""
    dt = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], token, axis=0).astype(dt)
    b = x.shape[0]
    period, n_per, tail = _period_counts(cfg)
    w = cfg.sliding_window
    pos = jnp.broadcast_to(jnp.asarray(pos, jnp.int32).reshape(-1), (b,))
    wpos = pos % w if w else pos
    keep = None if live is None else jnp.asarray(live).reshape(-1) > 0

    def body(x, inp):
        period_p, pc = inp
        period_p = _cast(period_p, dt)

        def rec_body(x, rec_inp):
            rec_p, h, conv = rec_inp
            y, new = rec_layer_decode(x, rec_p, cfg, {"h": h, "conv": conv},
                                      policy=policy)
            hnew, cnew = new["h"], new["conv"].astype(jnp.float32)
            if keep is not None:
                hnew = jnp.where(keep[:, None], hnew, h)
                cnew = jnp.where(keep[:, None, None], cnew, conv)
            return y, (hnew, cnew)

        x, (hs, convs) = jax.lax.scan(
            rec_body, x, (period_p["recs"], pc["rec_h"], pc["rec_conv"]),
            unroll=cfg.unroll_scans)
        x, pk, pv = attn_layer_decode_paged(x, period_p["attn"], cfg,
                                            pc["k"], pc["v"], tables, pos,
                                            wpos, policy=policy, live=live)
        return x, {"rec_h": hs, "rec_conv": convs, "k": pk, "v": pv}

    n_per = cfg.n_layers // cfg.attn_period
    x, pcache = jax.lax.scan(body, x, (params["periods"], cache["periods"]),
                             unroll=n_per if cfg.unroll_scans else 1)
    new_cache = {"periods": pcache}
    if tail:
        def tail_body(x, inp):
            rec_p, h, conv = inp
            y, new = rec_layer_decode(x, rec_p, cfg,
                                      {"h": h, "conv": conv}, policy=policy)
            hnew, cnew = new["h"], new["conv"].astype(jnp.float32)
            if keep is not None:
                hnew = jnp.where(keep[:, None], hnew, h)
                cnew = jnp.where(keep[:, None, None], cnew, conv)
            return y, {"h": hnew, "conv": cnew}
        x, tcache = jax.lax.scan(
            tail_body, x, (_cast(params["tail"], dt), cache["tail"]["h"],
                           cache["tail"]["conv"]), unroll=cfg.unroll_scans)
        new_cache["tail"] = tcache
    x = norm_apply(x, params["ln_f"], cfg.norm, cfg.norm_eps)
    ldt = jnp.bfloat16 if cfg.logits_mm_dtype == "bf16" else jnp.float32
    logits = jnp.einsum("bsd,dv->bsv", x.astype(ldt),
                        params["unembed"].astype(ldt),
                        preferred_element_type=jnp.float32)
    return mask_padded_logits(logits, cfg.vocab), new_cache


def decode_step(params, cfg, token, cache, pos, *, policy=None, live=None):
    """One decode step. ``pos`` is a scalar (whole batch at one position)
    or a per-slot (B,) vector — the continuous-batching engine's slots
    each advance independently through their own ring-buffer cursor.
    ``live`` (B,) masks dead rows' state updates: recurrent snapshots
    pass through bit-untouched and ring writes park at PARKED_POS."""
    dt = jnp.dtype(cfg.compute_dtype)
    x = jnp.take(params["embed"], token, axis=0).astype(dt)
    b = x.shape[0]
    period, n_per, tail = _period_counts(cfg)
    w = cfg.sliding_window
    pos = jnp.asarray(pos, jnp.int32)
    wpos = pos % w if w else pos
    keep = None if live is None else jnp.asarray(live).reshape(-1) > 0
    drop = live is not None
    if drop:
        # Park AFTER the ring wrap — a post-modulo position is always in
        # range, so masking before the wrap would alias into the ring.
        wpos = jnp.where(keep,
                         jnp.broadcast_to(
                             jnp.asarray(wpos, jnp.int32).reshape(-1), (b,)),
                         PARKED_POS)

    def body(x, inp):
        period_p, pc = inp
        period_p = _cast(period_p, dt)

        def rec_body(x, rec_inp):
            rec_p, h, conv = rec_inp
            y, new = rec_layer_decode(x, rec_p, cfg, {"h": h, "conv": conv},
                                      policy=policy)
            hnew, cnew = new["h"], new["conv"].astype(jnp.float32)
            if keep is not None:
                hnew = jnp.where(keep[:, None], hnew, h)
                cnew = jnp.where(keep[:, None, None], cnew, conv)
            return y, (hnew, cnew)

        x, (hs, convs) = jax.lax.scan(
            rec_body, x, (period_p["recs"], pc["rec_h"], pc["rec_conv"]),
            unroll=cfg.unroll_scans)
        x, ck, cv = attn_layer_decode(x, period_p["attn"], cfg,
                                      pc["k"], pc["v"], pos, wpos,
                                      policy=policy, oob_drop=drop)
        return x, {"rec_h": hs, "rec_conv": convs, "k": ck, "v": cv}

    n_per = cfg.n_layers // cfg.attn_period
    x, pcache = jax.lax.scan(body, x, (params["periods"], cache["periods"]),
                             unroll=n_per if cfg.unroll_scans else 1)
    new_cache = {"periods": pcache}
    if tail:
        def tail_body(x, inp):
            rec_p, h, conv = inp
            y, new = rec_layer_decode(x, rec_p, cfg,
                                      {"h": h, "conv": conv}, policy=policy)
            hnew, cnew = new["h"], new["conv"].astype(jnp.float32)
            if keep is not None:
                hnew = jnp.where(keep[:, None], hnew, h)
                cnew = jnp.where(keep[:, None, None], cnew, conv)
            return y, {"h": hnew, "conv": cnew}
        x, tcache = jax.lax.scan(
            tail_body, x, (_cast(params["tail"], dt), cache["tail"]["h"],
                           cache["tail"]["conv"]), unroll=cfg.unroll_scans)
        new_cache["tail"] = tcache
    x = norm_apply(x, params["ln_f"], cfg.norm, cfg.norm_eps)
    ldt = jnp.bfloat16 if cfg.logits_mm_dtype == "bf16" else jnp.float32
    logits = jnp.einsum("bsd,dv->bsv", x.astype(ldt),
                        params["unembed"].astype(ldt),
                        preferred_element_type=jnp.float32)
    return mask_padded_logits(logits, cfg.vocab), new_cache
