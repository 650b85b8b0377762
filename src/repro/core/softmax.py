"""Softmax built on the VEXP exponential, plus online (partial) softmax algebra.

Implements the paper's optimized kernel structure (§IV-C):

  MAX  — row max (numerical stability),
  EXP  — vexp(x - max) with fused sum accumulation,
  NORM — one reciprocal per row, then pointwise multiply
         (never a per-element divide; Snitch's divider is unpipelined and the
         TPU VPU's divide is similarly much slower than multiply).

The *online* variants maintain FlashAttention-style running statistics
(m = running max, l = running sum of exponentials) and a merge rule that is
associative and commutative — the same algebra the paper uses for partial
softmax on SPM tiles, and that we additionally exploit for sequence-parallel
(KV-sharded) decode where each shard computes partial (m, l, acc) and the
merge happens through an all-reduce.
"""

from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

from .vexp import get_exp_fn


def softmax(x: jax.Array, axis: int = -1, *, exp_impl: str | Callable = "vexp",
            where=None, policy=None) -> jax.Array:
    """Numerically-stable softmax with a pluggable exp backend.

    exp_impl: "vexp" (paper's approximation), "exact" (transcendental),
    "vexp_hw" (bit-exact hardware model), or a callable.

    An ``ExecPolicy`` overrides exp_impl and, for ``kernel_backend=
    "pallas"`` (unmasked case), routes to the fused Pallas row-softmax via
    kernels.dispatch — one switch flips the whole execution.
    """
    if policy is not None:
        if policy.kernel_backend == "pallas" and where is None:
            from repro.kernels.dispatch import dispatch
            return dispatch("softmax", policy)(x, axis=axis, policy=policy)
        exp_impl = policy.exp_backend
    exp_fn = exp_impl if callable(exp_impl) else get_exp_fn(exp_impl)
    if where is not None:
        x = jnp.where(where, x, -jnp.inf)
    m = jax.lax.stop_gradient(jnp.max(x, axis=axis, keepdims=True))
    m = jnp.where(jnp.isfinite(m), m, 0.0)  # all-masked rows
    with jax.named_scope("exp"):
        e = exp_fn(x - m)
    if where is not None:
        e = jnp.where(where, e, 0.0)
    s = jnp.sum(e, axis=axis, keepdims=True)
    # NORM: reciprocal once, multiply everywhere. Guarded like the kernels'
    # finalize: a fully-masked row (all where=False — e.g. a padded serving
    # slot) has s == 0, and an unguarded divide would emit inf * 0 = NaN;
    # with the guard its e is all-zero, so the row comes out zeros.
    return e * (1.0 / jnp.maximum(s, 1e-30))


def log_softmax(x: jax.Array, axis: int = -1, *,
                exp_impl: str | Callable = "vexp") -> jax.Array:
    """log softmax; the log itself stays exact (only exp is approximated)."""
    exp_fn = exp_impl if callable(exp_impl) else get_exp_fn(exp_impl)
    m = jax.lax.stop_gradient(jnp.max(x, axis=axis, keepdims=True))
    shifted = x - m
    s = jnp.sum(exp_fn(shifted), axis=axis, keepdims=True)
    return shifted - jnp.log(s)


class SoftmaxStats(NamedTuple):
    """Online softmax running statistics for a row (or batch of rows)."""
    m: jax.Array    # running max
    l: jax.Array    # running sum of exp(x - m)


def stats_init(shape, dtype=jnp.float32) -> SoftmaxStats:
    return SoftmaxStats(m=jnp.full(shape, -jnp.inf, dtype),
                        l=jnp.zeros(shape, dtype))


def stats_update(stats: SoftmaxStats, x_blk: jax.Array, axis: int = -1, *,
                 exp_fn: Callable) -> tuple[SoftmaxStats, jax.Array, jax.Array]:
    """Absorb one block of scores; returns (new_stats, p_blk, alpha).

    p_blk = exp(x_blk - m_new) and alpha = exp(m_old - m_new) is the
    correction factor the caller applies to any accumulator keyed on m_old
    (the FlashAttention-2 rescale).
    """
    m_blk = jnp.max(x_blk, axis=axis)
    m_new = jnp.maximum(stats.m, m_blk)
    # Guard -inf - -inf = nan for fully-masked blocks.
    safe_m = jnp.where(jnp.isfinite(m_new), m_new, 0.0)
    alpha = exp_fn(jnp.where(jnp.isfinite(stats.m), stats.m - safe_m, -jnp.inf))
    alpha = jnp.where(jnp.isfinite(stats.m), alpha, 0.0)
    p_blk = exp_fn(x_blk - jnp.expand_dims(safe_m, axis))
    p_blk = jnp.where(jnp.isfinite(x_blk), p_blk, 0.0)
    l_new = stats.l * alpha + jnp.sum(p_blk, axis=axis)
    return SoftmaxStats(m=m_new, l=l_new), p_blk, alpha


def stats_merge(a: SoftmaxStats, b: SoftmaxStats, *,
                exp_fn: Callable) -> tuple[SoftmaxStats, jax.Array, jax.Array]:
    """Merge two partial softmaxes; returns (merged, alpha_a, alpha_b).

    alpha_* rescale accumulators built against each partial max. Associative
    + commutative, so it is safe inside tree reductions / all-reduces
    (sequence-parallel decode) exactly like the paper's tile merge.
    """
    m = jnp.maximum(a.m, b.m)
    safe_m = jnp.where(jnp.isfinite(m), m, 0.0)

    def _alpha(mm):
        al = exp_fn(jnp.where(jnp.isfinite(mm), mm - safe_m, -jnp.inf))
        return jnp.where(jnp.isfinite(mm), al, 0.0)

    aa, ab = _alpha(a.m), _alpha(b.m)
    return SoftmaxStats(m=m, l=a.l * aa + b.l * ab), aa, ab


# Finite "empty" sentinel used by the Pallas kernels instead of -inf (keeps
# the vexp bit-twiddle NaN-free). Anything at or below half of it is treated
# as "this shard saw no valid key".
KERNEL_NEG_INF = -1e30


def stats_merge_collective(stats: SoftmaxStats, acc: jax.Array,
                           axis_name: str, *,
                           exp_fn: Callable) -> tuple[SoftmaxStats, jax.Array]:
    """``stats_merge`` as an SPMD collective over a ``shard_map`` mesh axis.

    Each shard holds partial (m, l) statistics plus an un-normalized
    accumulator ``acc`` (trailing dims broadcast against l's). Because the
    merge rule is associative and commutative, folding it over all shards
    is exactly one ``pmax`` (global m) followed by one ``psum`` of the
    alpha-rescaled (l, acc) — the all-reduce form of the paper's partial
    softmax tile merge, applied to sequence-parallel flash decode.

    Shards whose slice contained no valid key carry the identity element
    (m <= KERNEL_NEG_INF, l = 0, acc = 0) or (m = -inf); both are guarded
    so they contribute exactly nothing (never NaN via -inf - -inf).

    This is the *split* (three-collective: pmax + 2 psum) merge strategy;
    ``stats_merge_collective_packed`` is the single-collective form over a
    packed (acc | m | l) tile. Both compute the exact same algebra.
    """
    m_g = jax.lax.pmax(stats.m, axis_name)
    empty = (stats.m <= 0.5 * KERNEL_NEG_INF) | ~jnp.isfinite(stats.m)
    safe_g = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
    alpha = jnp.where(empty, 0.0, exp_fn(stats.m - safe_g))
    l_g = jax.lax.psum(stats.l * alpha, axis_name)
    acc_g = jax.lax.psum(acc * alpha, axis_name)
    return SoftmaxStats(m=m_g, l=l_g), acc_g


def stats_merge_collective_packed(packed: jax.Array, axis_name: str, *,
                                  exp_fn: Callable
                                  ) -> tuple[SoftmaxStats, jax.Array]:
    """Single-collective partial-softmax merge over a packed stats tile.

    ``packed`` is each shard's contiguous ``(..., d + 2)`` tile laid out
    as ``[acc (d lanes) | m (1) | l (1)]`` — emitted directly by the
    flash-decode kernel's packed mode, so there is no per-shard
    concatenate before the collective. One ``all_gather`` over
    ``axis_name`` moves every shard's tile in a single collective, and
    the alpha-rescaled fold of ``stats_merge`` then runs shard-locally
    over the gathered leading axis.

    The global max is taken *before* any exponentiation, so ``m - m_g``
    is always <= 0 and the merge cannot overflow no matter how far the
    per-shard maxima are spread (the overflow-guard test pins this).
    Empty shards (m <= KERNEL_NEG_INF / non-finite) contribute exactly
    nothing, as in the split form.

    Returns the same (SoftmaxStats, acc) pair as
    ``stats_merge_collective``; callers normalize with
    ``acc / max(l, tiny)``.
    """
    d = packed.shape[-1] - 2
    tiles = jax.lax.all_gather(packed, axis_name)    # (n_shards, ..., d+2)
    m_sh = tiles[..., d:d + 1]
    l_sh = tiles[..., d + 1:d + 2]
    m_g = jnp.max(m_sh, axis=0)
    empty = (m_sh <= 0.5 * KERNEL_NEG_INF) | ~jnp.isfinite(m_sh)
    safe_g = jnp.where(jnp.isfinite(m_g), m_g, 0.0)
    alpha = jnp.where(empty, 0.0, exp_fn(m_sh - safe_g))
    l_g = jnp.sum(l_sh * alpha, axis=0)
    acc_g = jnp.sum(tiles[..., :d] * alpha, axis=0)
    return SoftmaxStats(m=m_g, l=l_g), acc_g
