"""Fused decode-attention Pallas kernel (flash-decode, VEXP partial softmax).

Substantiates EXPERIMENTS.md §Perf iteration C4: one decode step reads the
KV cache exactly once from HBM — the (m, l, acc) online-softmax statistics
live in VMEM scratch across the KV-block sweep, and the cache is consumed
in its storage dtype (bf16) with f32 accumulation (``accum_dtype="bfloat16"``
drops the scratch statistics to bf16 for the memory/accuracy trade the
ExecPolicy exposes).

Two cache layouts share one kernel body: head-major "bhsd" (B, Hkv, S, d)
— the §Perf C3 layout — and sequence-major "bshd" (B, S, nH * d) with the
heads folded into the lanes; the BlockSpec index maps place the KV-sweep
axis wherever the layout stores it, so neither layout pays a materialized
transpose.

Grid = (nB, nH, nS) with the KV sweep innermost; each program handles one
lane block's query rows (GQA: G = H // Hkv rows per KV head; a "bshd"
block of 128 lanes holding several heads gets their rows block-diagonal,
see ``ops._group_q``) for a *block* of ``block_b`` batch rows — decode
dots are tiny (G × block_s), so batching rows into the block amortizes
grid/DMA bookkeeping across the slot pool instead of paying it per row.
``block_b`` is clamped so the K/V blocks stay a few MB of VMEM.

``cache_len`` is a per-batch-row (B,) vector in SMEM: each row of a block
masks the KV sweep against its own length, so a continuous-batching server
can decode slots whose requests are at different positions in one program
(ragged slot lengths never touch each other's cache rows), and whole KV
blocks past every row's length are skipped.

Sequence parallelism (the paper's §IV-C partial-softmax algebra as an SPMD
primitive): in *partial* mode the kernel emits the raw per-shard
(m, l, acc) statistics instead of the normalized output, and masks its KV
sweep in **global** coordinates via ``seq_offset`` (an SMEM scalar: the
absolute position of this shard's first cache row). *Packed* partial mode
goes one step further and lands the statistics in ONE contiguous
(B, nH, G, d+2) tile laid out ``[acc | m | l]`` — the exact buffer the
single-collective merge (``core.softmax.stats_merge_collective_packed``)
all_gathers, so no stat array is ever concatenated outside the kernel.
Shards are merged under ``shard_map`` per the policy's merge strategy —
see ``ops.decode_attention_sharded``.

Sliding windows mask ``cache_len - window <= kpos < cache_len`` (exactly
``window`` tokens including the current one); KV blocks entirely outside
the window are skipped, so a windowed decode over a long linear cache does
O(window) work like the ring-buffer path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.vexp import get_exp_fn
# The finite "empty" sentinel must be the SAME value stats_merge_collective
# classifies empty shards against — single-sourced in core.softmax.
from repro.core.softmax import KERNEL_NEG_INF as NEG_INF

DEFAULT_BLOCK_S = 512
DEFAULT_BLOCK_B = 8

_ACCUM_DTYPES = {"float32": jnp.float32, "bfloat16": jnp.bfloat16}


def _decode_kernel(len_ref, off_ref, q_ref, k_ref, v_ref, *refs,
                   block_b: int, block_s: int, ns: int, s_valid: int,
                   sm_scale: float, exp_impl: str, window, layout: str,
                   partial: bool, packed: bool = False):
    if packed:
        op_ref, m_ref, l_ref, acc_ref = refs
    elif partial:
        om_ref, ol_ref, oacc_ref, m_ref, l_ref, acc_ref = refs
    else:
        (o_ref, m_ref, l_ref, acc_ref) = refs
    bi = pl.program_id(0)
    si = pl.program_id(2)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # per-row lengths of this row block, kept as scalars: Mosaic cannot
    # reshape a stacked (block_b,) vector into a (block_b, 1, 1) column.
    lens = [len_ref[bi * block_b + i] for i in range(block_b)]
    seq_off = off_ref[0]
    start = si * block_s                 # shard-local block start
    g_start = start + seq_off            # absolute cache position
    exp_fn = get_exp_fn(exp_impl)

    # Block-level liveness: any (row, key) pair inside [len - window, len)?
    def row_live(ln):
        live = g_start < ln
        if window is not None:
            # first in-window position; blocks fully below it are skipped,
            # so the sweep effectively starts at max(0, len - window)'s
            # block.
            live &= (g_start + block_s) > (ln - window)
        return live

    live = functools.reduce(jnp.logical_or, map(row_live, lens))

    @pl.when(live)
    def _compute():
        q = q_ref[:, 0].astype(jnp.float32) * sm_scale     # (bb, G, d)
        if layout == "bhsd":
            k = k_ref[:, 0]                                # (bb, bs, d)
            v = v_ref[:, 0]
        else:                        # "bshd", heads folded into the lanes
            k = k_ref[...]
            v = v_ref[...]
        s = jax.lax.dot_general(
            q.astype(k.dtype), k, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)            # (bb, G, bs)
        lpos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        kpos = lpos + seq_off
        row = jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        lcol = jnp.full(s.shape, lens[0], jnp.int32)
        for i in range(1, block_b):
            lcol = jnp.where(row == i, lens[i], lcol)
        keep = kpos < lcol
        # shard-local padding rows (lpos >= s_valid) may sit at absolute
        # positions that *are* valid on later shards — mask them explicitly.
        keep &= lpos < s_valid
        if window is not None:
            keep &= kpos >= lcol - window
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[...].astype(jnp.float32)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = exp_fn(m_prev - m_new)
        p = exp_fn(s - m_new)
        p = jnp.where(keep, p, 0.0)
        l_ref[...] = (l_ref[...].astype(jnp.float32) * alpha
                      + jnp.sum(p, -1, keepdims=True)).astype(l_ref.dtype)
        acc_ref[...] = (acc_ref[...].astype(jnp.float32) * alpha
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v,
                            (((2,), (1,)), ((0,), (0,))),
                            preferred_element_type=jnp.float32)
                        ).astype(acc_ref.dtype)
        m_ref[...] = m_new.astype(m_ref.dtype)

    @pl.when(si == ns - 1)
    def _finalize():
        if packed:
            # one contiguous (block_b, G, d+2) tile per shard laid out as
            # [acc | m | l]: the collective merge gathers this buffer
            # whole — no post-hoc concatenate of three stat arrays on the
            # host side of the kernel.
            op_ref[:, 0] = jnp.concatenate(
                [acc_ref[...].astype(op_ref.dtype),
                 m_ref[...].astype(op_ref.dtype),
                 l_ref[...].astype(op_ref.dtype)], axis=-1)
        elif partial:
            # raw shard statistics: rows this shard never touched stay at
            # (m=NEG_INF, l=0, acc=0) — the merge's identity element.
            om_ref[:, 0] = m_ref[...].astype(om_ref.dtype)
            ol_ref[:, 0] = l_ref[...].astype(ol_ref.dtype)
            oacc_ref[:, 0] = acc_ref[...].astype(oacc_ref.dtype)
        else:
            inv = 1.0 / jnp.maximum(l_ref[...].astype(jnp.float32), 1e-30)
            o_ref[:, 0] = (acc_ref[...].astype(jnp.float32)
                           * inv).astype(o_ref.dtype)


def resolve_block_b(b: int, block_s: int, d: int) -> int:
    """Rows per grid cell: amortize grid overhead, cap K/V block VMEM at a
    few MB (block_b * block_s * d * 2 arrays)."""
    bb = min(b, DEFAULT_BLOCK_B)
    while bb > 1 and bb * block_s * d * 4 * 2 > 8 * 1024 * 1024:
        bb //= 2
    while b % bb:            # b is padded to a block multiple by ops
        bb //= 2
    return max(bb, 1)


def _specs(layout: str, block_b: int, g: int, bs: int, d: int):
    """(smem, q, k/v) BlockSpecs for the given layout; grid (nB, nH, nS).
    "bshd" caches arrive as (B, S, nH * d): one head's or lane block's
    (bs, d) tile is lane-aligned, where a (bs, 1, d) block of a 4-D array
    would break the (8, 128) tiling rule on its minor dims."""
    from jax.experimental.pallas import tpu as pltpu
    q_spec = pl.BlockSpec((block_b, 1, g, d),
                          lambda bb, hh, si: (bb, hh, 0, 0))
    if layout == "bhsd":
        kv_spec = pl.BlockSpec((block_b, 1, bs, d),
                               lambda bb, hh, si: (bb, hh, si, 0))
    else:
        kv_spec = pl.BlockSpec((block_b, bs, d),
                               lambda bb, hh, si: (bb, si, hh))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return smem, q_spec, kv_spec


def _scratch(block_b: int, g: int, d: int, accum_dtype: str):
    from jax.experimental.pallas import tpu as pltpu
    adt = _ACCUM_DTYPES[accum_dtype]
    return [pltpu.VMEM((block_b, g, 1), adt),
            pltpu.VMEM((block_b, g, 1), adt),
            pltpu.VMEM((block_b, g, d), adt)]


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_s", "s_valid", "interpret", "exp_impl", "window",
    "layout", "accum_dtype"))
def decode_attention_kernel(q, k_cache, v_cache, cache_len, seq_offset, *,
                            sm_scale: float, s_valid: int,
                            block_s: int = DEFAULT_BLOCK_S,
                            interpret: bool = False,
                            exp_impl: str = "vexp",
                            window=None, layout: str = "bhsd",
                            accum_dtype: str = "float32"):
    """q: (B, nH, G, d) query rows per head or lane block; caches:
    (B, nH, S, d) ("bhsd") or (B, S, nH * d) ("bshd"); cache_len: (B,)
    int32 per-row valid lengths (broadcast a scalar before calling);
    seq_offset: (1,) int32 absolute position of this cache slice's first
    row (zero when unsharded); s_valid: unpadded cache length (padded rows
    above it are never attended). Returns (B, nH, G, d). S divisible by
    block_s, B by the row block; d a multiple of 128 — all handled by
    ops."""
    b, hkv, g, d = q.shape
    smax = k_cache.shape[2] if layout == "bhsd" else k_cache.shape[1]
    bs = min(block_s, smax)
    ns = smax // bs
    bb = resolve_block_b(b, bs, d)
    kernel = functools.partial(
        _decode_kernel, block_b=bb, block_s=bs, ns=ns, s_valid=s_valid,
        sm_scale=sm_scale, exp_impl=exp_impl, window=window, layout=layout,
        partial=False)
    smem, q_spec, kv_spec = _specs(layout, bb, g, bs, d)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        grid=(b // bb, hkv, ns),
        in_specs=[smem, smem, q_spec, kv_spec, kv_spec],
        out_specs=pl.BlockSpec((bb, 1, g, d),
                               lambda bb_, hh, si: (bb_, hh, 0, 0)),
        scratch_shapes=_scratch(bb, g, d, accum_dtype),
        interpret=interpret,
    )(cache_len, seq_offset, q, k_cache, v_cache)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_s", "s_valid", "interpret", "exp_impl", "window",
    "layout", "accum_dtype"))
def decode_attention_kernel_partial(q, k_cache, v_cache, cache_len,
                                    seq_offset, *, sm_scale: float,
                                    s_valid: int,
                                    block_s: int = DEFAULT_BLOCK_S,
                                    interpret: bool = False,
                                    exp_impl: str = "vexp",
                                    window=None, layout: str = "bhsd",
                                    accum_dtype: str = "float32"):
    """Partial-statistics mode: same sweep, but emits the shard's raw
    (m, l, acc) — shapes (B, nH, G, 1) ×2 and (B, nH, G, d), all f32 —
    with masking done in *global* positions (``seq_offset`` + local index
    against the global ``cache_len``). A shard whose slice lies entirely
    outside [cache_len - window, cache_len) returns the merge identity
    (NEG_INF, 0, 0)."""
    b, hkv, g, d = q.shape
    smax = k_cache.shape[2] if layout == "bhsd" else k_cache.shape[1]
    bs = min(block_s, smax)
    ns = smax // bs
    bb = resolve_block_b(b, bs, d)
    kernel = functools.partial(
        _decode_kernel, block_b=bb, block_s=bs, ns=ns, s_valid=s_valid,
        sm_scale=sm_scale, exp_impl=exp_impl, window=window, layout=layout,
        partial=True)
    smem, q_spec, kv_spec = _specs(layout, bb, g, bs, d)
    stat = pl.BlockSpec((bb, 1, g, 1), lambda bb_, hh, si: (bb_, hh, 0, 0))
    return pl.pallas_call(
        kernel,
        out_shape=[
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
            jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
        ],
        grid=(b // bb, hkv, ns),
        in_specs=[smem, smem, q_spec, kv_spec, kv_spec],
        out_specs=[stat, stat,
                   pl.BlockSpec((bb, 1, g, d),
                                lambda bb_, hh, si: (bb_, hh, 0, 0))],
        scratch_shapes=_scratch(bb, g, d, accum_dtype),
        interpret=interpret,
    )(cache_len, seq_offset, q, k_cache, v_cache)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "block_s", "s_valid", "interpret", "exp_impl", "window",
    "layout", "accum_dtype"))
def decode_attention_kernel_packed(q, k_cache, v_cache, cache_len,
                                   seq_offset, *, sm_scale: float,
                                   s_valid: int,
                                   block_s: int = DEFAULT_BLOCK_S,
                                   interpret: bool = False,
                                   exp_impl: str = "vexp",
                                   window=None, layout: str = "bhsd",
                                   accum_dtype: str = "float32"):
    """Packed partial-statistics mode: the same sweep as
    ``decode_attention_kernel_partial`` but the shard's raw statistics
    land in ONE contiguous f32 tile of shape (B, nH, G, d + 2), laid out
    ``[acc | m | l]`` along the last axis — the unit the single-collective
    merge (``core.softmax.stats_merge_collective_packed``) all_gathers.
    The two stat lanes ride beyond ``d``; the merge slices them off after
    the fold, so the accumulator's lane padding stays untouched."""
    b, hkv, g, d = q.shape
    smax = k_cache.shape[2] if layout == "bhsd" else k_cache.shape[1]
    bs = min(block_s, smax)
    ns = smax // bs
    bb = resolve_block_b(b, bs, d)
    kernel = functools.partial(
        _decode_kernel, block_b=bb, block_s=bs, ns=ns, s_valid=s_valid,
        sm_scale=sm_scale, exp_impl=exp_impl, window=window, layout=layout,
        partial=True, packed=True)
    smem, q_spec, kv_spec = _specs(layout, bb, g, bs, d)
    return pl.pallas_call(
        kernel,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d + 2), jnp.float32),
        grid=(b // bb, hkv, ns),
        in_specs=[smem, smem, q_spec, kv_spec, kv_spec],
        out_specs=pl.BlockSpec((bb, 1, g, d + 2),
                               lambda bb_, hh, si: (bb_, hh, 0, 0)),
        scratch_shapes=_scratch(bb, g, d, accum_dtype),
        interpret=interpret,
    )(cache_len, seq_offset, q, k_cache, v_cache)


def decode_attention_bhsd(q, k_cache, v_cache, cache_len, *, sm_scale: float,
                          block_s: int = DEFAULT_BLOCK_S,
                          interpret: bool = False, exp_impl: str = "vexp"):
    """Back-compat alias for the head-major unsharded kernel."""
    return decode_attention_kernel(
        q, k_cache, v_cache, cache_len, jnp.zeros((1,), jnp.int32),
        sm_scale=sm_scale, s_valid=k_cache.shape[2], block_s=block_s,
        interpret=interpret, exp_impl=exp_impl)


# -------------------------------------------------------------- paged sweep
#
# Block-table indirection: the KV "cache" is a pool of fixed-size physical
# pages — "bshd": (N, page, Hkv, d), "bhsd": (N, Hkv, page, d) — and each
# batch row owns a row of ``block_tab`` (B, nS) int32 mapping its logical
# page index to a physical pool page. The table rides in as a
# scalar-prefetch argument (SMEM), so the K/V BlockSpec index maps read
# ``tab[b, si]`` to drive the page DMA — the sweep walks a row's *logical*
# pages while fetching wherever the allocator placed them, and the online
# softmax math is unchanged from the contiguous kernel.
#
# The grid is (B, nS) with ALL KV heads folded into one block (decode
# pages are tiny, so fetching every head's slice of a page in one cell
# amortizes grid/DMA bookkeeping the way ``block_b`` row-batching does for
# the contiguous sweep — per-row tables make row-batching impossible).
# Entries of ``block_tab`` past a row's allocated extent must point at a
# real (reserved/scratch) page: the index map always fetches, compute is
# masked by ``cache_len``.

def _paged_kernel(tab_ref, len_ref, off_ref, q_ref, k_ref, v_ref, *refs,
                  page: int, ns: int, sm_scale: float, exp_impl: str,
                  window, layout: str, partial: bool, packed: bool = False):
    if packed:
        op_ref, m_ref, l_ref, acc_ref = refs
    elif partial:
        om_ref, ol_ref, oacc_ref, m_ref, l_ref, acc_ref = refs
    else:
        o_ref, m_ref, l_ref, acc_ref = refs
    bi = pl.program_id(0)
    si = pl.program_id(1)

    @pl.when(si == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    ln = len_ref[bi]
    seq_off = off_ref[0]
    g_start = si * page + seq_off        # absolute position of this page
    exp_fn = get_exp_fn(exp_impl)
    live = g_start < ln
    if window is not None:
        live &= (g_start + page) > (ln - window)

    @pl.when(live)
    def _compute():
        q = q_ref[0].astype(jnp.float32) * sm_scale       # (Hkv, G, d)
        k = k_ref[0]          # (Hkv, page, d) bhsd / (page, Hkv, d) bshd
        v = v_ref[0]
        if layout == "bhsd":
            kdims = (((2,), (2,)), ((0,), (0,)))
            vdims = (((2,), (1,)), ((0,), (0,)))
        else:                                             # "bshd"
            kdims = (((2,), (2,)), ((0,), (1,)))
            vdims = (((2,), (0,)), ((0,), (1,)))
        s = jax.lax.dot_general(q.astype(k.dtype), k, kdims,
                                preferred_element_type=jnp.float32)
        # (Hkv, G, page)
        kpos = g_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 2)
        keep = kpos < ln
        if window is not None:
            keep &= kpos >= ln - window
        s = jnp.where(keep, s, NEG_INF)
        m_prev = m_ref[...].astype(jnp.float32)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        alpha = exp_fn(m_prev - m_new)
        p = exp_fn(s - m_new)
        p = jnp.where(keep, p, 0.0)
        l_ref[...] = (l_ref[...].astype(jnp.float32) * alpha
                      + jnp.sum(p, -1, keepdims=True)).astype(l_ref.dtype)
        acc_ref[...] = (acc_ref[...].astype(jnp.float32) * alpha
                        + jax.lax.dot_general(
                            p.astype(v.dtype), v, vdims,
                            preferred_element_type=jnp.float32)
                        ).astype(acc_ref.dtype)
        m_ref[...] = m_new.astype(m_ref.dtype)

    @pl.when(si == ns - 1)
    def _finalize():
        if packed:
            op_ref[0] = jnp.concatenate(
                [acc_ref[...].astype(op_ref.dtype),
                 m_ref[...].astype(op_ref.dtype),
                 l_ref[...].astype(op_ref.dtype)], axis=-1)
        elif partial:
            om_ref[0] = m_ref[...].astype(om_ref.dtype)
            ol_ref[0] = l_ref[...].astype(ol_ref.dtype)
            oacc_ref[0] = acc_ref[...].astype(oacc_ref.dtype)
        else:
            inv = 1.0 / jnp.maximum(l_ref[...].astype(jnp.float32), 1e-30)
            o_ref[0] = (acc_ref[...].astype(jnp.float32)
                        * inv).astype(o_ref.dtype)


def _paged_call(q, k_pool, v_pool, block_tab, cache_len, seq_offset, *,
                sm_scale, interpret, exp_impl, window, layout, accum_dtype,
                partial, packed):
    from jax.experimental.pallas import tpu as pltpu
    b, hkv, g, d = q.shape
    page = k_pool.shape[2] if layout == "bhsd" else k_pool.shape[1]
    ns = block_tab.shape[1]
    kernel = functools.partial(
        _paged_kernel, page=page, ns=ns, sm_scale=sm_scale,
        exp_impl=exp_impl, window=window, layout=layout, partial=partial,
        packed=packed)
    q_spec = pl.BlockSpec((1, hkv, g, d),
                          lambda bi, si, tab, ln, off: (bi, 0, 0, 0))
    if layout == "bhsd":
        kv_spec = pl.BlockSpec(
            (1, hkv, page, d),
            lambda bi, si, tab, ln, off: (tab[bi, si], 0, 0, 0))
    else:
        kv_spec = pl.BlockSpec(
            (1, page, hkv, d),
            lambda bi, si, tab, ln, off: (tab[bi, si], 0, 0, 0))
    out_map = lambda bi, si, tab, ln, off: (bi, 0, 0, 0)   # noqa: E731
    adt = _ACCUM_DTYPES[accum_dtype]
    scratch = [pltpu.VMEM((hkv, g, 1), adt), pltpu.VMEM((hkv, g, 1), adt),
               pltpu.VMEM((hkv, g, d), adt)]
    if packed:
        out_shape = jax.ShapeDtypeStruct((b, hkv, g, d + 2), jnp.float32)
        out_specs = pl.BlockSpec((1, hkv, g, d + 2), out_map)
    elif partial:
        out_shape = [jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
                     jax.ShapeDtypeStruct((b, hkv, g, 1), jnp.float32),
                     jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32)]
        stat = pl.BlockSpec((1, hkv, g, 1), out_map)
        out_specs = [stat, stat, pl.BlockSpec((1, hkv, g, d), out_map)]
    else:
        out_shape = jax.ShapeDtypeStruct(q.shape, q.dtype)
        out_specs = pl.BlockSpec((1, hkv, g, d), out_map)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3, grid=(b, ns),
        in_specs=[q_spec, kv_spec, kv_spec], out_specs=out_specs,
        scratch_shapes=scratch)
    return pl.pallas_call(kernel, grid_spec=grid_spec, out_shape=out_shape,
                          interpret=interpret)(
        block_tab, cache_len, seq_offset, q, k_pool, v_pool)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "interpret", "exp_impl", "window", "layout", "accum_dtype"))
def decode_attention_kernel_paged(q, k_pool, v_pool, block_tab, cache_len,
                                  seq_offset, *, sm_scale: float,
                                  interpret: bool = False,
                                  exp_impl: str = "vexp", window=None,
                                  layout: str = "bshd",
                                  accum_dtype: str = "float32"):
    """Paged flash-decode. q: (B, Hkv, G, d); pools: (N, page, Hkv, d)
    ("bshd") or (N, Hkv, page, d) ("bhsd"); block_tab: (B, nS) int32
    physical page per logical page (entries past a row's extent must
    reference a valid reserved page); cache_len: (B,) int32; seq_offset:
    (1,) int32 absolute position of logical page 0 (shard-local tables).
    Returns (B, Hkv, G, d)."""
    return _paged_call(q, k_pool, v_pool, block_tab, cache_len, seq_offset,
                       sm_scale=sm_scale, interpret=interpret,
                       exp_impl=exp_impl, window=window, layout=layout,
                       accum_dtype=accum_dtype, partial=False, packed=False)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "interpret", "exp_impl", "window", "layout", "accum_dtype"))
def decode_attention_kernel_paged_partial(q, k_pool, v_pool, block_tab,
                                          cache_len, seq_offset, *,
                                          sm_scale: float,
                                          interpret: bool = False,
                                          exp_impl: str = "vexp",
                                          window=None, layout: str = "bshd",
                                          accum_dtype: str = "float32"):
    """Paged partial-statistics sweep: raw (m, l, acc) per shard, masked in
    global coordinates — the paged counterpart of
    ``decode_attention_kernel_partial`` (block tables shard with the
    sequence axis, so each shard sweeps its local table slice)."""
    return _paged_call(q, k_pool, v_pool, block_tab, cache_len, seq_offset,
                       sm_scale=sm_scale, interpret=interpret,
                       exp_impl=exp_impl, window=window, layout=layout,
                       accum_dtype=accum_dtype, partial=True, packed=False)


@functools.partial(jax.jit, static_argnames=(
    "sm_scale", "interpret", "exp_impl", "window", "layout", "accum_dtype"))
def decode_attention_kernel_paged_packed(q, k_pool, v_pool, block_tab,
                                         cache_len, seq_offset, *,
                                         sm_scale: float,
                                         interpret: bool = False,
                                         exp_impl: str = "vexp",
                                         window=None, layout: str = "bshd",
                                         accum_dtype: str = "float32"):
    """Paged packed partial mode: one contiguous (B, Hkv, G, d+2) f32
    [acc | m | l] tile per shard — the single-collective merge unit."""
    return _paged_call(q, k_pool, v_pool, block_tab, cache_len, seq_offset,
                       sm_scale=sm_scale, interpret=interpret,
                       exp_impl=exp_impl, window=window, layout=layout,
                       accum_dtype=accum_dtype, partial=True, packed=True)
