"""Kernel dispatch table + shape-bucketed block-size autotuner.

One entry point — ``dispatch(op, policy)`` — maps every numeric op in the
stack onto the implementation the ``ExecPolicy`` selects:

    op                        pallas                      reference            xla
    -----------------------   -------------------------  ------------------   ----
    vexp                      kernels.vexp (tiled)        core vexp (untiled)  same
    softmax                   kernels.softmax (fused)     core softmax         core
    flash_attention           kernels.flash_attention     core attention_flash core attention_xla
    decode_attention          kernels.decode_attention    core decode          core decode
    decode_attention_sharded  shard_map partial +         core decode (GSPMD)  core decode (GSPMD)
                              packed/split stats merge

All returned callables accept ``policy=`` and thread the policy's exp
backend / block sizes / interpret flag down to the kernel bodies, so a
single policy switch flips numerics end to end. ``decode_attention``
implementations (all three backends) accept a scalar *or* per-slot
``(B,)`` ``cache_len`` — the serving engine's continuous-batching
contract — and mask each batch row against its own length.

Autotuning: ``autotune_policy(op, policy, *shapes)`` times a small set of
candidate block sizes on first sight of a (device, op, shape-bucket) key and
memoizes the winner, so repeated shapes never re-time. Shape buckets round
dims up to powers of two — production serving sees few buckets even under
ragged batching. Winners additionally persist to disk (JSON at
``$REPRO_AUTOTUNE_CACHE``, default ``~/.cache/repro/autotune.json``;
``off`` disables) keyed by (device_kind, op, shape_bucket, policy), loaded
lazily on the first lookup — a serving restart on the same device kind
skips re-timing entirely.
"""

from __future__ import annotations

import functools
import json
import math
import os
import tempfile
import time
from typing import Callable, Dict, Optional, Tuple

import jax

from repro.analysis.registry import hot_path
from repro.runtime.policy import ExecPolicy

# ------------------------------------------------------------------ registry

# (op, backend) -> "module:function". Lazy import paths keep this module
# free of circular imports (ops modules import dispatch for autotuning).
_TABLE: Dict[Tuple[str, str], str] = {}

OPS = ("vexp", "softmax", "flash_attention", "decode_attention",
       "decode_attention_sharded", "decode_attention_paged")


def register(op: str, backend: str, target: str) -> None:
    _TABLE[(op, backend)] = target


def _load(target: str) -> Callable:
    mod_name, fn_name = target.split(":")
    mod = __import__(mod_name, fromlist=[fn_name])
    return getattr(mod, fn_name)


register("vexp", "pallas", "repro.kernels.vexp.ops:vexp")
register("vexp", "reference", "repro.kernels.dispatch:_vexp_fallback")
register("vexp", "xla", "repro.kernels.dispatch:_vexp_fallback")

register("softmax", "pallas", "repro.kernels.softmax.ops:softmax")
register("softmax", "reference", "repro.kernels.dispatch:_softmax_fallback")
register("softmax", "xla", "repro.kernels.dispatch:_softmax_fallback")

register("flash_attention", "pallas",
         "repro.kernels.flash_attention.ops:flash_attention_policy")
register("flash_attention", "reference",
         "repro.kernels.dispatch:_attention_reference")
register("flash_attention", "xla", "repro.kernels.dispatch:_attention_xla")

register("decode_attention", "pallas",
         "repro.kernels.decode_attention.ops:decode_attention_policy")
register("decode_attention", "reference",
         "repro.kernels.dispatch:_decode_fallback")
register("decode_attention", "xla", "repro.kernels.dispatch:_decode_fallback")

# Sequence-parallel decode over a KV cache sharded along S: the pallas
# backend runs the partial-stats kernel per shard + the psum stats merge
# under shard_map; the other backends express the same reduction in jnp
# and let GSPMD lower the sharded max/sum to the partial-softmax merge.
register("decode_attention_sharded", "pallas",
         "repro.kernels.decode_attention.ops:decode_attention_sharded")
register("decode_attention_sharded", "reference",
         "repro.kernels.dispatch:_decode_sharded_fallback")
register("decode_attention_sharded", "xla",
         "repro.kernels.dispatch:_decode_sharded_fallback")

# Paged decode over a block pool + per-row block table: the pallas backend
# drives the page DMA from the scalar-prefetched table inside the kernel;
# the reference/xla backends materialize the gather (pool[tab]) and run
# the contiguous core reduction — same semantics, one extra copy.
register("decode_attention_paged", "pallas",
         "repro.kernels.decode_attention.ops:decode_attention_paged_policy")
register("decode_attention_paged", "reference",
         "repro.kernels.dispatch:_decode_paged_fallback")
register("decode_attention_paged", "xla",
         "repro.kernels.dispatch:_decode_paged_fallback")


def dispatch(op: str, policy: ExecPolicy) -> Callable:
    """The callable implementing ``op`` under ``policy``.

    The returned function takes the op's arrays/kwargs plus ``policy=``;
    callers pass the same policy through (it is a static jit argument in
    the Pallas wrappers, so each policy compiles once and caches).
    """
    if op not in OPS:
        raise ValueError(f"unknown op {op!r}; one of {OPS}")
    try:
        target = _TABLE[(op, policy.kernel_backend)]
    except KeyError:
        raise ValueError(
            f"no implementation registered for op={op!r} "
            f"backend={policy.kernel_backend!r}")
    return _load(target)


# ------------------------------------------ non-pallas backend adapters

def _vexp_fallback(x, *, policy: ExecPolicy):
    """reference/xla vexp: the untiled core datapath (XLA fuses it)."""
    return policy.exp_fn()(x)


def exp_callable(policy: Optional[ExecPolicy] = None,
                 exp_impl: str = "vexp") -> Callable:
    """Elementwise exp for model-internal gates under a policy.

    The recurrent families' exponentials — the RG-LRU gate
    ``a = exp(c·r·log a)``, the SSD decays/softplus and the SiLU gates —
    are the softmax-free sites where the paper's exp-backend choice still
    applies. This is their one resolution rule: ``policy.exp_backend``
    wins, the legacy ``exp_impl`` config string is the fallback — so a
    serving ``--policy-groups`` spec flips recurrent-gate numerics exactly
    like it flips attention softmax numerics. Every kernel backend
    resolves to the core datapath here: gates fuse into the surrounding
    elementwise work under XLA, and a per-gate ``pallas_call`` would cost
    more than the exp itself (the tiled kernel stays reserved for the
    standalone ``vexp`` op above).
    """
    from repro.core.vexp import get_exp_fn
    return get_exp_fn(policy.exp_backend if policy is not None else exp_impl)


def _softmax_fallback(x, axis=-1, *, policy: ExecPolicy):
    from repro.core.softmax import softmax as core_softmax
    return core_softmax(x, axis=axis, exp_impl=policy.exp_backend)


def _attention_reference(q, k, v, *, causal=True, window=None, sm_scale=None,
                         policy: ExecPolicy):
    from repro.core.attention import attention_flash
    return attention_flash(q, k, v, causal=causal, window=window,
                           sm_scale=sm_scale, exp_impl=policy.exp_backend,
                           block_k=policy.block_k)


def _attention_xla(q, k, v, *, causal=True, window=None, sm_scale=None,
                   policy: ExecPolicy):
    from repro.core.attention import attention_xla
    return attention_xla(q, k, v, causal=causal, window=window,
                         sm_scale=sm_scale, exp_impl=policy.exp_backend)


@hot_path
def _decode_fallback(q, k_cache, v_cache, cache_len, *, window=None,
                     sm_scale=None, layout="bshd", policy: ExecPolicy):
    from repro.core.attention import decode_attention
    return decode_attention(q, k_cache, v_cache, cache_len, window=window,
                            sm_scale=sm_scale, exp_impl=policy.exp_backend,
                            layout=layout)


@hot_path
def _decode_paged_fallback(q, k_pool, v_pool, block_tab, cache_len, *,
                           window=None, sm_scale=None, layout="bshd",
                           policy: ExecPolicy):
    """reference/xla paged decode: gather the block table to a contiguous
    per-row cache and run the core reduction (the oracle semantics of the
    paged pallas sweep)."""
    from repro.core.attention import decode_attention
    from repro.kernels.decode_attention.ops import paged_gather
    k = paged_gather(k_pool, block_tab, layout)
    v = paged_gather(v_pool, block_tab, layout)
    return decode_attention(q, k, v, cache_len, window=window,
                            sm_scale=sm_scale, exp_impl=policy.exp_backend,
                            layout=layout)


@hot_path
def _decode_sharded_fallback(q, k_cache, v_cache, cache_len, *, mesh=None,
                             seq_axis="model", window=None, sm_scale=None,
                             layout="bshd", policy: ExecPolicy):
    """reference/xla sharded decode: the core reduction is written as pure
    max/sum over the cache's S axis, so jit + GSPMD lowers a seq-sharded
    cache to per-shard partials + all-reduce without explicit collectives
    (mesh/seq_axis are accepted for signature parity and unused)."""
    from repro.core.attention import decode_attention
    return decode_attention(q, k_cache, v_cache, cache_len, window=window,
                            sm_scale=sm_scale, exp_impl=policy.exp_backend,
                            layout=layout)


# ----------------------------------------------------------------- autotune

# Candidate block sizes per op. Each candidate is a dict of policy-field
# overrides; the tuner clamps to the workload in the ops wrappers (kernels
# min() blocks against actual dims).
CANDIDATES = {
    "softmax": [{"block_rows": r} for r in (32, 64, 128, 256)],
    "vexp": [{"block_rows": r} for r in (128, 256, 512)],
    "flash_attention": [{"block_q": q, "block_k": k}
                        for q, k in ((64, 64), (128, 128),
                                     (128, 256), (256, 128))],
    "decode_attention": [{"block_s": s} for s in (256, 512, 1024)],
    # Sequence-parallel decode tunes the *merge strategy*: one packed
    # all_gather of the contiguous (acc | m | l) tile vs the pmax + 2×psum
    # split form. Same algebra; the winner is interconnect-dependent.
    "decode_attention_sharded": [{"merge_strategy": "packed"},
                                 {"merge_strategy": "split"}],
    # Paged decode tunes the page size — but only at POOL CONSTRUCTION
    # (the page is the pool's physical block shape; DecodeState times
    # candidates on a synthetic pool before allocating the real one).
    "decode_attention_paged": [{"block_page": p} for p in (32, 64, 128)],
}

# repr((device_kind, op, shape_bucket, policy_sans_blocks)) -> winning
# overrides. String keys so the cache round-trips through JSON unchanged:
# the in-process winners are persisted to disk and re-loaded on the next
# process start, so serving restarts skip re-timing entirely.
_AUTOTUNE_CACHE: Dict[str, dict] = {}
_STATS = {"hits": 0, "misses": 0, "disk_loaded": 0}
_DISK_STATE = {"loaded": False}

# Path resolution: $REPRO_AUTOTUNE_CACHE (a file path; "off"/"0" disables
# persistence) -> ~/.cache/repro/autotune.json.
_DISK_ENV = "REPRO_AUTOTUNE_CACHE"
_CACHE_VERSION = 1


def autotune_cache_path() -> Optional[str]:
    raw = os.environ.get(_DISK_ENV, "").strip()
    if raw.lower() in ("0", "off", "none", "disabled"):
        return None
    if raw:
        return raw
    return os.path.join(os.path.expanduser("~"), ".cache", "repro",
                        "autotune.json")


def load_autotune_cache(path: Optional[str] = None) -> int:
    """Merge the on-disk autotune cache into the in-process one (in-process
    entries win). Returns the number of entries loaded; missing/corrupt
    files load nothing. Called lazily on the first autotune lookup."""
    _DISK_STATE["loaded"] = True
    path = path if path is not None else autotune_cache_path()
    if not path:
        return 0
    try:
        with open(path) as fh:
            payload = json.load(fh)
        entries = payload.get("entries", {})
    except (OSError, ValueError):
        return 0
    n = 0
    for key, overrides in entries.items():
        if isinstance(key, str) and isinstance(overrides, dict) \
                and key not in _AUTOTUNE_CACHE:
            _AUTOTUNE_CACHE[key] = overrides
            n += 1
    _STATS["disk_loaded"] += n
    return n


def save_autotune_cache(path: Optional[str] = None) -> Optional[str]:
    """Atomically persist the in-process cache; best-effort (a read-only
    filesystem must never break serving). Returns the path written.

    Concurrent-serve safe: the write goes through a private tmpfile +
    ``os.replace`` (readers never observe a torn file), and the entries a
    *different* process persisted since we last read the file are merged
    back in before writing (in-process winners take precedence on key
    collisions — both processes timed the same bucket, either answer is
    valid). Two engines racing the JSON therefore converge on the union
    of their winners instead of the last writer clobbering the first.
    """
    path = path if path is not None else autotune_cache_path()
    if not path or not _AUTOTUNE_CACHE:
        return None
    try:
        cache_dir = os.path.dirname(path) or "."
        os.makedirs(cache_dir, exist_ok=True)
        merged: Dict[str, dict] = {}
        try:
            with open(path) as fh:
                on_disk = json.load(fh).get("entries", {})
            merged.update({k: v for k, v in on_disk.items()
                           if isinstance(k, str) and isinstance(v, dict)})
        except (OSError, ValueError, AttributeError):
            pass                      # missing/corrupt file: start fresh
        merged.update(_AUTOTUNE_CACHE)
        fd, tmp = tempfile.mkstemp(dir=cache_dir, prefix=".autotune-")
        try:
            with os.fdopen(fd, "w") as fh:
                json.dump({"version": _CACHE_VERSION, "entries": merged},
                          fh, indent=1)
            os.replace(tmp, path)
        except BaseException:
            try:
                os.unlink(tmp)        # never leave tmp droppings behind
            except OSError:
                pass
            raise
        return path
    except OSError:
        return None


def autotune_cache_stats() -> dict:
    return dict(_STATS, entries=len(_AUTOTUNE_CACHE))


def autotune_cache_clear() -> None:
    _AUTOTUNE_CACHE.clear()
    _STATS["hits"] = 0
    _STATS["misses"] = 0
    _STATS["disk_loaded"] = 0
    _DISK_STATE["loaded"] = False


def _bucket_dim(n: int) -> int:
    return 1 if n <= 1 else 1 << (n - 1).bit_length()


def shape_bucket(*arrays) -> tuple:
    """Pow2-rounded shape+dtype key; ragged shapes share few buckets."""
    return tuple((tuple(_bucket_dim(d) for d in a.shape), str(a.dtype))
                 for a in arrays)


def _device_kind() -> str:
    dev = jax.devices()[0]
    return f"{dev.platform}:{getattr(dev, 'device_kind', '')}"


def _time_call(fn, n_warmup=1, n_timed=3) -> float:
    for _ in range(n_warmup):
        jax.block_until_ready(fn())
    best = math.inf
    for _ in range(n_timed):
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        best = min(best, time.perf_counter() - t0)
    return best


def autotune_policy(op: str, policy: ExecPolicy, run: Callable[[ExecPolicy], object],
                    *arrays) -> ExecPolicy:
    """Return ``policy`` with block sizes tuned for these array shapes.

    ``run(candidate_policy)`` must execute the op end to end (the ops
    wrappers pass a closure over their own jitted kernel). First call per
    (device, op, shape bucket) times every candidate; later calls are pure
    cache hits — no re-timing on a repeated shape.

    Timing is only meaningful eagerly: under an outer jit trace the arrays
    are tracers and wall-clock would measure tracing, not the kernel. In
    that case return the cached winner if one exists for this bucket
    (tuned eagerly earlier, e.g. by a warmup call) and otherwise fall back
    to the policy's static block sizes without polluting the cache.
    """
    base = policy.replace(autotune=False)
    if not _DISK_STATE["loaded"]:
        load_autotune_cache()
    # Block sizes are what's being tuned, so key on everything else.
    key = repr((_device_kind(), op, shape_bucket(*arrays),
                (base.exp_backend, base.kernel_backend, base.accum_dtype,
                 base.interpret)))
    cached = _AUTOTUNE_CACHE.get(key)
    if cached is not None:
        _STATS["hits"] += 1
        return base.replace(**cached)
    if any(isinstance(a, jax.core.Tracer) for a in arrays):
        return base
    _STATS["misses"] += 1
    best_overrides, best_t, error = {}, math.inf, None
    for overrides in CANDIDATES.get(op, [{}]):
        cand = base.replace(**overrides)
        try:
            t = _time_call(lambda: run(cand))
        except Exception as e:
            error = e       # candidate invalid for this shape; skip
            continue
        if t < best_t:
            best_t, best_overrides = t, overrides
    if best_t == math.inf:
        raise RuntimeError(
            f"autotune {op}: every candidate failed") from error
    _AUTOTUNE_CACHE[key] = best_overrides
    save_autotune_cache()
    return base.replace(**best_overrides)
