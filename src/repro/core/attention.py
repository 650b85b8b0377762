"""Attention with VEXP softmax: reference, FlashAttention-2, and decode paths.

Shape convention: q, k, v are (B, S, H, D) / (B, S, H_kv, D). GQA is handled
by grouping query heads over KV heads (no materialized KV repeat).

Three implementations, selected by ``impl``:

``"xla"``     plain materialized-scores attention (oracle; XLA fuses this
              well for short sequences under remat),
``"flash"``   FlashAttention-2 structured scan over KV blocks with online
              (m, l) statistics — the paper's partial softmax (§III-B/IV-D),
``"pallas"``  the Pallas TPU kernel (kernels/flash_attention), gated behind
              a flag because this container lowers for CPU.

``decode_attention`` is the single-token path used by serve_step: it supports
a sequence-sharded KV cache (sequence-parallel "flash-decode"); because it is
written as max/sum reductions over the cache's sequence axis, GSPMD lowers
the sharded reduction to the partial-softmax merge + all-reduce automatically.
"""

from __future__ import annotations

import functools
import math
from typing import Callable, Optional

import jax
import jax.numpy as jnp

from .vexp import get_exp_fn

NEG_INF = -1e30  # finite mask value: keeps vexp branches NaN-free


def _resolve(exp_impl) -> Callable:
    return exp_impl if callable(exp_impl) else get_exp_fn(exp_impl)


def _gqa_scores(q: jax.Array, k: jax.Array, scale: float) -> jax.Array:
    """(B,Sq,H,D) x (B,Sk,Hkv,D) -> scores (B, Hkv, G, Sq, Sk)."""
    b, sq, h, d = q.shape
    hkv = k.shape[2]
    g = h // hkv
    qg = q.reshape(b, sq, hkv, g, d)
    return jnp.einsum("bskgd,btkd->bkgst", qg, k) * scale


def _mask(sq: int, sk: int, *, causal: bool, window: Optional[int],
          q_offset: int | jax.Array = 0) -> Optional[jax.Array]:
    """Boolean (Sq, Sk) mask (True = keep). q_offset is the absolute position
    of q[0] minus that of k[0] (for prefill/decode with caches); a (B,)
    array gives each batch row its own offset (chunked prefill cursors) and
    widens the mask to (B, Sq, Sk)."""
    if not causal and window is None:
        return None
    qoff = jnp.asarray(q_offset)
    if qoff.ndim:
        qpos = jnp.arange(sq)[None, :, None] + qoff.reshape(-1, 1, 1)
        kpos = jnp.arange(sk)[None, None, :]
    else:
        qpos = jnp.arange(sq)[:, None] + qoff
        kpos = jnp.arange(sk)[None, :]
    keep = kpos <= qpos if causal else jnp.ones_like(kpos <= qpos)
    if window is not None:
        keep &= kpos > qpos - window
    return keep


def attention_xla(q, k, v, *, causal=True, window=None, exp_impl="vexp",
                  q_offset=0, sm_scale=None, kv_valid=None):
    """Reference attention: materializes the score matrix.

    ``kv_valid`` is an optional (B, Sk) boolean mask of real (non-padding)
    key positions — padded prompt rows in a ragged serving batch must
    neither be attended nor contribute to the softmax normalizer.
    """
    exp_fn = _resolve(exp_impl)
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    s = _gqa_scores(q.astype(jnp.float32), k.astype(jnp.float32), scale)
    msk = _mask(q.shape[1], k.shape[1], causal=causal, window=window,
                q_offset=q_offset)
    if msk is not None and msk.ndim == 2:
        msk = msk[None]                            # -> (1|B, Sq, Sk)
    if kv_valid is not None:
        kvm = kv_valid[:, None, :]                 # (B, 1, Sk)
        msk = kvm if msk is None else msk & kvm
    if msk is not None:
        s = jnp.where(msk[:, None, None], s, NEG_INF)
    m = jax.lax.stop_gradient(jnp.max(s, axis=-1, keepdims=True))
    p = exp_fn(s - m)
    if msk is not None:
        p = jnp.where(msk[:, None, None], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p * (1.0 / jnp.maximum(l, 1e-30))          # NORM: reciprocal-multiply
    o = jnp.einsum("bkgst,btkd->bskgd", p, v.astype(jnp.float32))
    b, sq, hkv, g, dd = o.shape
    return o.reshape(b, sq, hkv * g, dd).astype(q.dtype)


def attention_flash(q, k, v, *, causal=True, window=None, exp_impl="vexp",
                    q_offset=0, sm_scale=None, block_k=512, unroll=False,
                    mm_dtype="f32", kv_valid=None):
    """FlashAttention-2-structured attention (pure JAX scan over KV blocks).

    Maintains per-row running (m, l, acc); each block applies the paper's
    partial-softmax update: rescale by exp(m_old - m_new), accumulate
    exp(s - m_new) and its V-weighted sum. Never materializes (Sq, Sk).

    mm_dtype="bf16" feeds the score/PV matmuls MXU-native bf16 inputs with
    f32 accumulation (preferred_element_type) — (m, l, acc) statistics stay
    f32, so only matmul *inputs* lose precision (§Perf iteration A1).

    ``kv_valid``: optional (B, Sk) boolean mask of real key positions —
    padding rows of a ragged prompt batch are masked out of every block's
    score/normalizer update.
    """
    exp_fn = _resolve(exp_impl)
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    mdt = jnp.bfloat16 if mm_dtype == "bf16" else jnp.float32
    block_k = min(block_k, sk)
    nblk = -(-sk // block_k)
    pad = nblk * block_k - sk
    if pad:
        kp = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vp = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    else:
        kp, vp = k, v
    kb = kp.reshape(b, nblk, block_k, hkv, d).transpose(1, 0, 2, 3, 4)
    vb = vp.reshape(b, nblk, block_k, hkv, d).transpose(1, 0, 2, 3, 4)
    if kv_valid is not None:
        kvp = jnp.pad(kv_valid, ((0, 0), (0, pad))) if pad else kv_valid
        kvb = kvp.reshape(b, nblk, block_k).transpose(1, 0, 2)
    else:
        # all-true single-row mask: broadcasts over batch, keeps one scan
        # body for both the masked and unmasked cases.
        kvb = jnp.ones((nblk, 1, block_k), bool)
    qg = (q.astype(jnp.float32) * scale).astype(mdt) \
        .reshape(b, sq, hkv, g, d)

    # q_offset may be a (B,) array (chunked prefill: per-slot cursors) —
    # qpos is then per-row and the block mask widens over the batch.
    qpos = jnp.arange(sq)[None, :] + jnp.asarray(q_offset).reshape(-1, 1)

    def body(carry, blk):
        m, l, acc = carry
        kblk, vblk, iblk, kvblk = blk
        s = jnp.einsum("bskgd,btkd->bkgst", qg, kblk.astype(mdt),
                       preferred_element_type=jnp.float32)
        kpos = iblk * block_k + jnp.arange(block_k)
        keep = jnp.broadcast_to(kpos[None, None, :] < sk,
                                (qpos.shape[0], sq, block_k))
        if causal:
            keep &= kpos[None, None, :] <= qpos[:, :, None]
        if window is not None:
            keep &= kpos[None, None, :] > qpos[:, :, None] - window
        keep = keep & kvblk[:, None, :]              # (B|1, Sq, bk)
        s = jnp.where(keep[:, None, None], s, NEG_INF)
        m_blk = jnp.max(s, axis=-1)
        m_new = jnp.maximum(m, m_blk)
        with jax.named_scope("exp"):
            alpha = exp_fn(m - m_new)
            p = exp_fn(s - m_new[..., None])
        p = jnp.where(keep[:, None, None], p, 0.0)
        l_new = l * alpha + jnp.sum(p, axis=-1)
        acc_new = acc * alpha[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p.astype(mdt), vblk.astype(mdt),
            preferred_element_type=jnp.float32)
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (kb, vb, jnp.arange(nblk), kvb), unroll=unroll)
    out = acc * (1.0 / jnp.maximum(l, 1e-30))[..., None]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d)
    return out.astype(q.dtype)


# ExecPolicy kernel backends -> legacy impl names (single source of truth).
from repro.runtime.policy import KERNEL_BACKEND_TO_ATTN_IMPL as _BACKEND_TO_IMPL  # noqa: E402,E501


def masked_policy(policy):
    """The policy for attention with per-row key lengths or a query offset
    (ragged, chunked and prefix-hit prefill): ``policy`` itself, except
    that a pallas policy runs on the reference flash scan, because the
    Pallas flash kernel masks keys from position 0 only."""
    if policy is None or policy.kernel_backend != "pallas":
        return policy
    return policy.replace(kernel_backend="reference", accum_dtype="float32")


def attention(q, k, v, *, causal=True, window=None, exp_impl="vexp",
              q_offset=0, sm_scale=None, impl="flash", block_k=512,
              unroll=False, mm_dtype="f32", kv_valid=None, policy=None):
    """Full-sequence attention with selectable implementation.

    A ``runtime.ExecPolicy`` (if given) decides impl, exp backend and block
    sizes in one object; the explicit keyword arguments remain for direct
    use.

    ``kv_valid``: optional (B, Sk) boolean key-validity mask for ragged
    (padded) prompt batches — masked key positions are excluded from both
    attention weights and the softmax normalizer. The Pallas kernel takes
    neither ``kv_valid`` nor a ``q_offset``: callers that need them pick
    their implementation through ``masked_policy``.
    """
    if policy is not None:
        impl = _BACKEND_TO_IMPL[policy.kernel_backend]
        exp_impl = policy.exp_backend
        block_k = policy.block_k
    if impl == "pallas" and (kv_valid is not None or
                             not (isinstance(q_offset, int) and q_offset == 0)):
        raise ValueError(
            "the Pallas flash kernel takes no per-row key lengths or query "
            "offset; run masked attention under masked_policy(policy)")
    if impl == "xla":
        return attention_xla(q, k, v, causal=causal, window=window,
                             exp_impl=exp_impl, q_offset=q_offset,
                             sm_scale=sm_scale, kv_valid=kv_valid)
    if impl == "flash":
        return attention_flash(q, k, v, causal=causal, window=window,
                               exp_impl=exp_impl, q_offset=q_offset,
                               sm_scale=sm_scale, block_k=block_k,
                               unroll=unroll, mm_dtype=mm_dtype,
                               kv_valid=kv_valid)
    if impl == "pallas":
        from repro.kernels.flash_attention import ops as fa_ops
        if policy is not None:
            return fa_ops.flash_attention_policy(
                q, k, v, causal=causal, window=window, sm_scale=sm_scale,
                policy=policy)
        return fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                      sm_scale=sm_scale)
    raise ValueError(f"unknown attention impl {impl!r}")


def decode_attention(q, k_cache, v_cache, cache_len, *, window=None,
                     exp_impl="vexp", sm_scale=None, mm_dtype="f32",
                     layout="bshd", policy=None):
    """Single-token decode attention over a (possibly sequence-sharded) cache.

    q: (B, 1, H, D); caches: (B, S_max, Hkv, D) or, as the serving pool
    stores them, (B, S_max, Hkv*D) ("bshd"), or (B, Hkv, S_max, D)
    ("bhsd"); cache_len: scalar or (B,) number of valid positions (the new
    token's K/V must already be written).

    Written as pure max/sum reductions over the cache sequence axis so that a
    cache sharded along S lowers to partial (m, l, acc) per shard + a cheap
    all-reduce merge — the paper's partial-softmax algebra as SPMD collective.

    A policy with ``kernel_backend="pallas"`` routes *every* configuration
    — both cache layouts, sliding windows, scalar or per-slot (B,)
    ``cache_len`` — to the fused flash-decode kernel (the layout is
    resolved in the kernel's index maps, windows in its sweep bounds);
    only the other backends run this reference reduction.
    """
    if policy is not None:
        exp_impl = policy.exp_backend
        cl = jnp.asarray(cache_len)
        if policy.kernel_backend == "pallas" and cl.ndim <= 1:
            from repro.kernels.decode_attention import ops as dec_ops
            return dec_ops.decode_attention_policy(
                q, k_cache, v_cache, cache_len, window=window,
                sm_scale=sm_scale, layout=layout, policy=policy)
    exp_fn = _resolve(exp_impl)
    b, _, h, d = q.shape
    if k_cache.ndim == 3:                  # lane-dense "bshd" pool layer
        k_cache = k_cache.reshape(*k_cache.shape[:2], -1, d)
        v_cache = v_cache.reshape(*v_cache.shape[:2], -1, d)
    if layout == "bhsd":
        hkv, smax = k_cache.shape[1], k_cache.shape[2]
    else:
        smax, hkv = k_cache.shape[1], k_cache.shape[2]
    g = h // hkv
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    mdt = jnp.bfloat16 if mm_dtype == "bf16" else jnp.float32
    qg = (q.astype(jnp.float32) * scale).astype(mdt).reshape(b, hkv, g, d)
    # cache reads stay in their storage dtype under mm_dtype="bf16": no
    # materialized f32 copy of the cache (§Perf iter C1); the "bhsd"
    # layout feeds the einsum directly — no cache transpose (§Perf C3)
    eq_s = "bkgd,bktd->bkgt" if layout == "bhsd" else "bkgd,btkd->bkgt"
    s = jnp.einsum(eq_s, qg, k_cache.astype(mdt),
                   preferred_element_type=jnp.float32)
    pos = jnp.arange(smax)
    cl = jnp.asarray(cache_len)
    keep = pos[None, :] < (cl.reshape(-1, 1) if cl.ndim else cl[None, None])
    if window is not None:
        start = (cl.reshape(-1, 1) if cl.ndim else cl[None, None]) - window
        keep = keep & (pos[None, :] >= start)
    s = jnp.where(keep[:, None, None, :], s, NEG_INF)
    m = jnp.max(s, axis=-1, keepdims=True)
    p = exp_fn(s - m)
    p = jnp.where(keep[:, None, None, :], p, 0.0)
    l = jnp.sum(p, axis=-1, keepdims=True)
    p = p * (1.0 / jnp.maximum(l, 1e-30))
    eq_o = "bkgt,bktd->bkgd" if layout == "bhsd" else "bkgt,btkd->bkgd"
    o = jnp.einsum(eq_o, p.astype(mdt), v_cache.astype(mdt),
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, h, d).astype(q.dtype)
