"""jit'd public wrappers: (B, 1, H, d) queries over a KV cache.

Policy-aware: ``decode_attention`` takes an ``ExecPolicy`` static argument
selecting exp backend, KV block size, accumulation dtype and interpret
mode; ``decode_attention_policy`` is the kernels.dispatch entry and applies
block-size autotuning when requested. Both cover every configuration the
serving engine produces — head-major ("bhsd") *and* sequence-major
("bshd") caches, scalar or per-slot (B,) ``cache_len``, and sliding
windows — with no reference fallback.

A sequence-major cache is read as the pool stores it, (B, S, Hkv*d) with
the heads folded into the lanes. Where d divides 128 one 128-lane block
holds 128 // d heads and their queries arrive block-diagonal
(``_group_q``), so the kernel reads the cache with no copy; a head dim
that does not divide 128 (and every "bhsd" cache) is lane-padded per
head, which copies the cache on each call.

``decode_attention_sharded`` is the sequence-parallel entry: a KV cache
sharded along its sequence axis over a mesh axis is swept shard-locally in
partial-statistics mode (each shard masks against its own slice of the
*global* ``cache_len`` via ``seq_offset``), and the per-shard statistics
merge under ``shard_map`` per ``policy.merge_strategy`` — "packed" (one
all_gather of a contiguous [acc | m | l] tile, a single collective) or
"split" (pmax + two psums) — the paper's §IV-C partial-softmax algebra as
an SPMD collective. ``decode_attention_partial_merged`` exposes the
shard-local sweep + merge for callers that run their own ``shard_map``
(the serving engine's sharded decode step).
"""

from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.runtime.policy import ExecPolicy
from .kernel import (decode_attention_kernel, decode_attention_kernel_partial,
                     decode_attention_kernel_packed, decode_attention_bhsd,
                     decode_attention_kernel_paged,
                     decode_attention_kernel_paged_partial,
                     decode_attention_kernel_paged_packed)

__all__ = ["decode_attention", "decode_attention_partial",
           "decode_attention_partial_packed",
           "decode_attention_partial_merged",
           "decode_attention_sharded", "decode_attention_policy",
           "decode_attention_bhsd", "decode_attention_paged",
           "decode_attention_paged_partial_merged",
           "decode_attention_paged_policy", "paged_gather"]


def _seq_axis(layout: str) -> int:
    return 2 if layout == "bhsd" else 1


def _heads_per_block(d: int, hkv: int) -> int:
    """Heads one 128-lane block of a sequence-major cache holds, as the
    kernel reads it unpadded: 1 where ``d`` fills whole lane tiles,
    ``128 // d`` where ``d`` divides 128 and the heads fill whole blocks,
    0 where neither holds (the cache is then lane-padded per head)."""
    if d % 128 == 0:
        return 1
    if 128 % d == 0 and hkv % (128 // d) == 0:
        return 128 // d
    return 0


def _group_q(qg, hp: int):
    """(B, Hkv, G, d) queries -> (B, Hkv/hp, hp*G, hp*d) block-diagonal
    rows: row j*G + g of lane block n holds head n*hp + j's query in lanes
    [j*d, (j+1)*d) and zeros elsewhere, so one dot against the block's
    K scores every head on its own lanes only. Built from whole rows
    repeated along the lanes and a mask, like ``_ungroup`` without a
    reshape that splits the lanes into (hp, d)."""
    b, hkv, g, d = qg.shape
    q = qg.reshape(b, hkv // hp, hp * g, d)
    q = jnp.concatenate([q] * hp, axis=-1)
    row = jax.lax.broadcasted_iota(jnp.int32, q.shape, 2)
    lane = jax.lax.broadcasted_iota(jnp.int32, q.shape, 3)
    return jnp.where(lane // d == row // g, q, jnp.zeros((), q.dtype))


def _ungroup(x, hp: int, d: int):
    """Kernel rows (B, Hkv/hp, hp*G, lanes) back to (B, Hkv, G, d): each
    row keeps its own head's lanes (lane-padded rows drop the pad).

    Plain slices of the 4-D rows: on TPU, XLA folded a reshape of the
    lanes into (hp, d) followed by these slices into one bitcast that
    keeps lanes [0, d) of every row, so every second head came out
    wrong (``tests/test_tpu_compile.py`` checks the compiled form)."""
    if hp == 1:
        return x[..., :d]
    b, nb, rows, _ = x.shape
    g = rows // hp
    x = jnp.stack([x[:, :, j * g:(j + 1) * g, j * d:(j + 1) * d]
                   for j in range(hp)], axis=2)       # (B, nb, hp, G, d)
    return x.reshape(b, nb * hp, g, d)


def _kv_heads(q, k_cache, layout) -> int:
    if layout == "bhsd":
        return k_cache.shape[1]
    if k_cache.ndim == 3:                  # (B, S, Hkv*d), heads in lanes
        return k_cache.shape[2] // q.shape[-1]
    return k_cache.shape[2]


def _lane_heads(q, k_cache, layout) -> int:
    """Heads per kernel row block: ``_heads_per_block`` for a "bshd"
    cache the kernel reads unpadded, else 1 (one lane-padded head)."""
    if layout == "bhsd":
        return 1
    return _heads_per_block(q.shape[-1], _kv_heads(q, k_cache, layout)) or 1


def _prepare(q, k_cache, v_cache, cache_len, block_s, layout):
    """Queries grouped as the kernel reads them, the cache viewed as it
    reads it, S block-padded, cache_len broadcast to (B,).

    A "bshd" cache is (B, S, Hkv*d) as the pool stores it, or (B, S, Hkv,
    d); the kernel reads (B, S, lanes) in 128-lane blocks. Where
    ``_heads_per_block`` is nonzero it reads the cache as it is and the
    queries come block-diagonal (``_group_q``); otherwise, and for
    "bhsd", each head is lane-padded to a multiple of 128 (a copy of the
    cache). Returns (q, k, v, cache_len, smax, hp); ``_ungroup`` maps
    the kernel's rows back to heads."""
    b, _, h, d = q.shape
    s_ax = _seq_axis(layout)
    smax = k_cache.shape[s_ax]
    hkv = _kv_heads(q, k_cache, layout)
    qg = q.reshape(b, hkv, h // hkv, d)
    hp = _heads_per_block(d, hkv) if layout == "bshd" else 0
    if hp:
        qp = qg if hp == 1 else _group_q(qg, hp)

        def lanes(x):
            return x.reshape(b, smax, hkv * d)
    else:
        hp, d_pad = 1, -(-d // 128) * 128
        qp = jnp.pad(qg, [(0, 0)] * 3 + [(0, d_pad - d)])

        def lanes(x):
            if layout == "bhsd":
                return jnp.pad(x, [(0, 0)] * 3 + [(0, d_pad - d)])
            x = jnp.pad(x.reshape(b, smax, hkv, d),
                        [(0, 0)] * 3 + [(0, d_pad - d)])
            return x.reshape(b, smax, hkv * d_pad)
    bs = min(block_s, smax)
    s_pad = -(-smax // bs) * bs

    def view(x):
        x = lanes(x)
        if s_pad == smax:
            return x
        pads = [(0, 0)] * x.ndim
        pads[s_ax] = (0, s_pad - smax)
        return jnp.pad(x, pads)

    clen = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1),
                            (b,))
    return qp, view(k_cache), view(v_cache), clen, smax, hp


def _policy_kernel_args(policy: Optional[ExecPolicy], block_s, interpret):
    exp_impl, accum = "vexp", "float32"
    if policy is not None:
        exp_impl = policy.exp_backend
        block_s = policy.block_s
        accum = policy.accum_dtype
        if interpret is None:
            interpret = policy.interpret_resolved()
    if interpret is None:
        interpret = jax.default_backend() == "cpu"
    return exp_impl, accum, block_s, interpret


@functools.partial(jax.jit, static_argnames=("window", "sm_scale", "layout",
                                             "block_s", "interpret",
                                             "policy"))
def decode_attention(q, k_cache, v_cache, cache_len, *, window=None,
                     sm_scale=None, layout="bhsd", block_s=512,
                     interpret=None, policy: Optional[ExecPolicy] = None):
    """Fused flash-decode. q: (B, 1, H, d); caches: (B, Hkv, S, d) ("bhsd")
    or (B, S, Hkv*d) / (B, S, Hkv, d) ("bshd"); cache_len: scalar int32
    or per-row (B,) int32 of valid positions (the serving engine's
    per-slot lengths); ``window``: static sliding window (attend exactly
    the last ``window`` positions of each row's valid range). Returns
    (B, 1, H, d)."""
    exp_impl, accum, block_s, interpret = _policy_kernel_args(
        policy, block_s, interpret)
    b, _, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qp, kp, vp, clen, smax, hp = _prepare(q, k_cache, v_cache, cache_len,
                                          block_s, layout)
    out = decode_attention_kernel(
        qp, kp, vp, clen, jnp.zeros((1,), jnp.int32), sm_scale=scale,
        s_valid=smax, block_s=block_s, interpret=interpret,
        exp_impl=exp_impl, window=window, layout=layout, accum_dtype=accum)
    return _ungroup(out, hp, d).reshape(b, 1, h, d)


@functools.partial(jax.jit, static_argnames=("window", "sm_scale", "layout",
                                             "block_s", "interpret",
                                             "policy"))
def decode_attention_partial(q, k_cache, v_cache, cache_len, seq_offset, *,
                             window=None, sm_scale=None, layout="bhsd",
                             block_s=512, interpret=None,
                             policy: Optional[ExecPolicy] = None):
    """Per-shard partial statistics for sequence-parallel decode.

    ``seq_offset`` (traced scalar int32) is the absolute cache position of
    this K/V slice's first row; ``cache_len`` stays *global*. Returns
    (m, l, acc): (B, Hkv, G, 1) ×2 and (B, Hkv, G, d), all f32 — merge
    with ``core.softmax.stats_merge_collective`` and normalize by
    ``acc / max(l, tiny)``.
    """
    exp_impl, accum, block_s, interpret = _policy_kernel_args(
        policy, block_s, interpret)
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qp, kp, vp, clen, smax, hp = _prepare(q, k_cache, v_cache, cache_len,
                                          block_s, layout)
    off = jnp.asarray(seq_offset, jnp.int32).reshape(1)
    m, l, acc = decode_attention_kernel_partial(
        qp, kp, vp, clen, off, sm_scale=scale, s_valid=smax,
        block_s=block_s, interpret=interpret, exp_impl=exp_impl,
        window=window, layout=layout, accum_dtype=accum)
    acc = _ungroup(acc, hp, d)
    stat = acc.shape[:3] + (1,)           # row statistics, one per head
    return m.reshape(stat), l.reshape(stat), acc


@functools.partial(jax.jit, static_argnames=("window", "sm_scale", "layout",
                                             "block_s", "interpret",
                                             "policy"))
def decode_attention_partial_packed(q, k_cache, v_cache, cache_len,
                                    seq_offset, *, window=None, sm_scale=None,
                                    layout="bhsd", block_s=512,
                                    interpret=None,
                                    policy: Optional[ExecPolicy] = None):
    """Per-shard partial statistics as ONE contiguous packed tile.

    Same sweep as ``decode_attention_partial`` but the kernel writes the
    shard's raw statistics directly into a single f32 buffer of the
    kernel's rows, (B, Hkv/hp, hp*G, lanes + 2) laid out
    ``[acc | m | l]`` — the unit the single-collective merge all_gathers
    whole. Rows merge independently; merge first, then map the
    accumulator back to heads with ``_ungroup`` (``_lane_heads`` gives
    hp; lanes outside a row's own head fold like any other).
    """
    exp_impl, accum, block_s, interpret = _policy_kernel_args(
        policy, block_s, interpret)
    d = q.shape[-1]
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qp, kp, vp, clen, smax, _ = _prepare(q, k_cache, v_cache, cache_len,
                                         block_s, layout)
    off = jnp.asarray(seq_offset, jnp.int32).reshape(1)
    return decode_attention_kernel_packed(
        qp, kp, vp, clen, off, sm_scale=scale, s_valid=smax,
        block_s=block_s, interpret=interpret, exp_impl=exp_impl,
        window=window, layout=layout, accum_dtype=accum)


def decode_attention_partial_merged(q, k_cache, v_cache, cache_len,
                                    seq_offset, *, seq_axis, window=None,
                                    sm_scale=None, layout="bhsd",
                                    policy: ExecPolicy):
    """Shard-local partial sweep + collective merge (call INSIDE shard_map).

    ``k_cache``/``v_cache`` are the *local* sequence slice; ``seq_offset``
    is the absolute position of its first row and ``cache_len`` stays
    global. The merge strategy comes from ``policy.merge_strategy``:

      "packed"  the kernel emits one contiguous [acc | m | l] tile and a
                single ``all_gather`` over ``seq_axis`` moves it — one
                collective per merge;
      "split"   the PR-3 form: pmax (global m) + two psums of the
                alpha-rescaled (l, acc) — three collectives.

    Both fold the exact same associative algebra; only the collective
    count (and fp summation order) differs. This is the one merge site
    shared by ``decode_attention_sharded`` and the serving engine's
    sharded ``decode_step``. Returns the normalized (B, 1, H, d) output.
    """
    from repro.core.softmax import (SoftmaxStats, stats_merge_collective,
                                    stats_merge_collective_packed)
    b, _, h, d = q.shape
    exp_fn = policy.exp_fn()
    if policy.merge_strategy == "packed":
        packed = decode_attention_partial_packed(
            q, k_cache, v_cache, cache_len, seq_offset, window=window,
            sm_scale=sm_scale, layout=layout, policy=policy)
        stats, acc = stats_merge_collective_packed(packed, seq_axis,
                                                   exp_fn=exp_fn)
        out = _ungroup(acc * (1.0 / jnp.maximum(stats.l, 1e-30)),
                       _lane_heads(q, k_cache, layout), d)
    else:
        m, l, acc = decode_attention_partial(
            q, k_cache, v_cache, cache_len, seq_offset, window=window,
            sm_scale=sm_scale, layout=layout, policy=policy)
        stats, acc = stats_merge_collective(
            SoftmaxStats(m=m, l=l), acc, seq_axis, exp_fn=exp_fn)
        out = acc * (1.0 / jnp.maximum(stats.l, 1e-30))
    return out.reshape(b, 1, h, d).astype(q.dtype)


@functools.lru_cache(maxsize=None)
def _sharded_program(mesh, seq_axis, window, sm_scale, layout: str,
                     policy: ExecPolicy):
    """One jitted shard_map program per (mesh, axis, window, scale, layout,
    policy) — eager shard_map would retrace the whole merge every call."""
    from jax.sharding import PartitionSpec as P

    s_ax = _seq_axis(layout)
    kv_spec = P(*([None] * s_ax), seq_axis)

    def _local(q, k, v, cl):
        local_s = k.shape[s_ax]
        off = jax.lax.axis_index(seq_axis) * local_s
        return decode_attention_partial_merged(
            q, k, v, cl, off, seq_axis=seq_axis, window=window,
            sm_scale=sm_scale, layout=layout, policy=policy)

    return jax.jit(jax.shard_map(
        _local, mesh=mesh,
        in_specs=(P(), kv_spec, kv_spec, P()),
        out_specs=P(), check_vma=False))


def decode_attention_sharded(q, k_cache, v_cache, cache_len, *, mesh,
                             seq_axis="model", window=None, sm_scale=None,
                             layout="bshd", policy: ExecPolicy):
    """Sequence-parallel flash decode over a KV cache sharded along S.

    The default layout is "bshd" — matching the dispatch table's
    reference/xla entries and ``cache_specs``, whose sequence sharding
    targets "bshd" caches (head-major caches shard heads when they divide
    the axis).

    q and ``cache_len`` are replicated; ``k_cache``/``v_cache`` are (or
    will be) sharded along their sequence axis over ``mesh``'s
    ``seq_axis``. Each shard runs the Pallas sweep in partial mode with
    ``seq_offset = axis_index * local_S`` and the shards merge per
    ``policy.merge_strategy``: "packed" gathers one contiguous
    [acc | m | l] tile in a single collective; "split" is the pmax + two
    psum form. Token-identical to the unsharded ``decode_attention``
    either way (the merge algebra is exact — only fp summation order
    differs). With ``policy.autotune`` the strategy is picked by timing
    both per (device_kind, shape_bucket) through the dispatch autotuner.
    """
    b = q.shape[0]
    clen = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1),
                            (b,))
    if policy.autotune:
        from repro.kernels.dispatch import autotune_policy
        policy = autotune_policy(
            "decode_attention_sharded", policy,
            lambda p: _sharded_program(mesh, seq_axis, window, sm_scale,
                                       layout, p)(q, k_cache, v_cache, clen),
            q, k_cache)
    fn = _sharded_program(mesh, seq_axis, window, sm_scale, layout, policy)
    return fn(q, k_cache, v_cache, clen)


# ------------------------------------------------------------ paged entries

def _prepare_paged(q, k_pool, v_pool, block_tab, cache_len, layout):
    """Group queries, lane-pad d (q AND pools), broadcast cache_len."""
    b, _, h, d = q.shape
    hkv = k_pool.shape[1] if layout == "bhsd" else k_pool.shape[2]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d)
    d_pad = -(-d // 128) * 128
    if d_pad != d:
        qg = jnp.pad(qg, [(0, 0)] * 3 + [(0, d_pad - d)])
        pad4 = [(0, 0)] * 3 + [(0, d_pad - d)]
        k_pool = jnp.pad(k_pool, pad4)
        v_pool = jnp.pad(v_pool, pad4)
    tab = jnp.asarray(block_tab, jnp.int32)
    clen = jnp.broadcast_to(jnp.asarray(cache_len, jnp.int32).reshape(-1),
                            (b,))
    return qg, k_pool, v_pool, tab, clen


@functools.partial(jax.jit, static_argnames=("window", "sm_scale", "layout",
                                             "interpret", "policy"))
def decode_attention_paged(q, k_pool, v_pool, block_tab, cache_len, *,
                           window=None, sm_scale=None, layout="bshd",
                           interpret=None,
                           policy: Optional[ExecPolicy] = None):
    """Paged flash-decode. q: (B, 1, H, d); pools: (N, page, Hkv, d)
    ("bshd") or (N, Hkv, page, d) ("bhsd"); ``block_tab`` (B, nS) int32
    maps each row's logical pages to physical pool pages (entries past a
    row's extent must reference a valid reserved page — the reserved
    scratch page 0 by convention); ``cache_len`` scalar or (B,) int32.
    The page size is whatever the pool was allocated with (a static shape
    here — never re-tuned per call). Returns (B, 1, H, d)."""
    exp_impl, accum, _, interpret = _policy_kernel_args(policy, 0, interpret)
    b, _, h, d = q.shape
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qg, kp, vp, tab, clen = _prepare_paged(q, k_pool, v_pool, block_tab,
                                           cache_len, layout)
    out = decode_attention_kernel_paged(
        qg, kp, vp, tab, clen, jnp.zeros((1,), jnp.int32), sm_scale=scale,
        interpret=interpret, exp_impl=exp_impl, window=window, layout=layout,
        accum_dtype=accum)
    return out[..., :d].reshape(b, 1, h, d)


def decode_attention_paged_partial_merged(q, k_pool, v_pool, block_tab,
                                          cache_len, seq_offset, *, seq_axis,
                                          window=None, sm_scale=None,
                                          layout="bshd",
                                          policy: ExecPolicy):
    """Shard-local paged sweep + collective merge (call INSIDE shard_map).

    The paged counterpart of ``decode_attention_partial_merged``: the pool
    holds this shard's *local* physical pages, ``block_tab`` its local
    (B, nS_local) table slice with local page ids, ``seq_offset`` the
    absolute position of local logical page 0; ``cache_len`` stays
    global. Statistics fold per ``policy.merge_strategy`` exactly like
    the contiguous path. Returns the normalized (B, 1, H, d) output."""
    from repro.core.softmax import (SoftmaxStats, stats_merge_collective,
                                    stats_merge_collective_packed)
    b, _, h, d = q.shape
    exp_impl, accum, _, interpret = _policy_kernel_args(policy, 0, None)
    scale = sm_scale if sm_scale is not None else 1.0 / math.sqrt(d)
    qg, kp, vp, tab, clen = _prepare_paged(q, k_pool, v_pool, block_tab,
                                           cache_len, layout)
    off = jnp.asarray(seq_offset, jnp.int32).reshape(1)
    exp_fn = policy.exp_fn()
    if policy.merge_strategy == "packed":
        packed = decode_attention_kernel_paged_packed(
            qg, kp, vp, tab, clen, off, sm_scale=scale, interpret=interpret,
            exp_impl=exp_impl, window=window, layout=layout,
            accum_dtype=accum)
        stats, acc = stats_merge_collective_packed(packed, seq_axis,
                                                   exp_fn=exp_fn)
        acc = acc[..., :d]
    else:
        m, l, acc = decode_attention_kernel_paged_partial(
            qg, kp, vp, tab, clen, off, sm_scale=scale, interpret=interpret,
            exp_impl=exp_impl, window=window, layout=layout,
            accum_dtype=accum)
        acc = acc[..., :d]
        stats, acc = stats_merge_collective(
            SoftmaxStats(m=m, l=l), acc, seq_axis, exp_fn=exp_fn)
    out = acc * (1.0 / jnp.maximum(stats.l, 1e-30))
    return out.reshape(b, 1, h, d).astype(q.dtype)


def paged_gather(pool, block_tab, layout="bshd"):
    """Materialize a contiguous per-row cache from a paged pool — the
    reference/xla semantics of block-table indirection (and the oracle
    the kernel tests compare against). Returns (B, nS*page, Hkv, d) for
    "bshd" pools, (B, Hkv, nS*page, d) for "bhsd"."""
    tab = jnp.asarray(block_tab, jnp.int32)
    b, ns = tab.shape
    gathered = pool[tab]                       # (B, nS, *page_shape)
    if layout == "bhsd":                       # (B, nS, Hkv, page, d)
        g = gathered.transpose(0, 2, 1, 3, 4)  # (B, Hkv, nS, page, d)
        return g.reshape(b, g.shape[1], ns * g.shape[3], g.shape[4])
    # "bshd": (B, nS, page, Hkv, d)
    return gathered.reshape(b, ns * gathered.shape[2], *gathered.shape[3:])


def decode_attention_paged_policy(q, k_pool, v_pool, block_tab, cache_len, *,
                                  window=None, sm_scale=None, layout="bshd",
                                  policy: ExecPolicy):
    """kernels.dispatch entry for the paged sweep (pallas backend).

    No per-call autotuning: the page size is baked into the pool's shape
    at allocation (``DecodeState`` tunes ``block_page`` once, *before*
    the pool exists)."""
    return decode_attention_paged(q, k_pool, v_pool, block_tab, cache_len,
                                  window=window, sm_scale=sm_scale,
                                  layout=layout, policy=policy)


def decode_attention_policy(q, k_cache, v_cache, cache_len, *, window=None,
                            sm_scale=None, layout="bhsd",
                            policy: ExecPolicy):
    """kernels.dispatch entry: policy-driven blocks + optional autotune.

    Covers every serving configuration — both cache layouts, sliding
    windows, scalar or per-slot cache lengths — through the fused kernel;
    there is no reference fallback."""
    if policy.autotune:
        from repro.kernels.dispatch import autotune_policy
        policy = autotune_policy(
            "decode_attention", policy,
            lambda p: decode_attention(q, k_cache, v_cache, cache_len,
                                       window=window, sm_scale=sm_scale,
                                       layout=layout, policy=p),
            q, k_cache)
    return decode_attention(q, k_cache, v_cache, cache_len, window=window,
                            sm_scale=sm_scale, layout=layout, policy=policy)
