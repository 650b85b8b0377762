"""Sharding rules: map every parameter/cache/batch leaf to a PartitionSpec.

Axes convention (launch/mesh.py):
  single pod:  ("data", "model") = (16, 16)
  multi pod:   ("pod", "data", "model") = (2, 16, 16)

"pod" behaves as an outer data-parallel axis; ``dp_axes(mesh)`` returns the
tuple of data axes present so specs written here work on both meshes.

Rules (TP = tensor parallel over "model"):
  * embeddings: vocab over model (row-parallel lookup);
  * attention: column-parallel wq / row-parallel wo; KV projections are
    replicated when n_kv_heads < |model| (GQA duplication — cheaper than
    splitting heads mid-dimension), sharded otherwise;
  * MLP: column-parallel in, row-parallel out (Megatron pattern — one
    all-reduce per block);
  * MoE: expert-parallel (experts over model) when E % |model| == 0, else
    TP-inside-expert (hidden over model);
  * SSM / RG-LRU: inner/recurrent width over model (all per-channel
    recurrences stay local);
  * FSDP (ZeRO-3 style) for large archs: remaining dim over "data";
    optimizer moments inherit parameter specs automatically.

Decode caches: KV sequence dim over model ("sequence-parallel flash
decode", powered by the paper's partial-softmax merge) when the batch is
too small to fill the data axes — selected per cell by ``cache_specs``.
``decode_kv_axis`` reports which mesh axis (if any) that left the cache's
S dim sharded over; callers hand it to ``decode_attention_sharded``
(kernels.dispatch), which sweeps each shard in partial-(m, l, acc) mode
and merges with the psum form of ``core.softmax.stats_merge`` — the fused
Pallas path now covers SPMD decode instead of falling back to the O(S)
reference reduction.
"""

from __future__ import annotations

import re

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def dp_axes(mesh: Mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def model_axis_size(mesh: Mesh) -> int:
    return mesh.shape["model"]


def batch_spec(mesh: Mesh) -> P:
    return P(dp_axes(mesh))


def _path_str(path) -> str:
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)


def param_specs(cfg, mesh: Mesh, *, fsdp: bool = False):
    """PartitionSpec pytree matching init_params(cfg)'s structure."""
    tp = model_axis_size(mesh)
    # shard KV projections only on clean head boundaries (GQA duplication
    # otherwise — replicating tiny KV heads beats mid-head splits)
    kv_shardable = bool(cfg.n_kv_heads) and cfg.n_kv_heads % tp == 0
    moe_ep = cfg.n_experts and cfg.n_experts % tp == 0

    def rule(path: str, leaf) -> P:
        nd = leaf.ndim
        # stacked layer arrays carry 1-2 leading layer axes; rules address
        # the trailing (true parameter) dims.
        def lead(n_param_dims):
            return (None,) * (nd - n_param_dims)

        if re.search(r"(^|/)(embed)$", path):
            return P("model", None)
        if re.search(r"pos_embed$", path):
            return P(None, None)
        if re.search(r"unembed$", path):
            return P(None, "model")
        if re.search(r"(wq|wg|wu|wx|wy|w_input_gate|w_rec_gate|in_proj|"
                     r"vis_proj)$", path):
            return P(*lead(2), None, "model")
        if re.search(r"(wo|wd|w_out|out_proj)$", path):
            return P(*lead(2), "model", None)
        if re.search(r"(wk|wv)$", path):
            return (P(*lead(2), None, "model") if kv_shardable
                    else P(*lead(2), None, None))
        if re.search(r"experts/(wg|wu)$", path):
            return (P(*lead(3), "model", None, None) if moe_ep
                    else P(*lead(3), None, None, "model"))
        if re.search(r"experts/wd$", path):
            return (P(*lead(3), "model", None, None) if moe_ep
                    else P(*lead(3), None, "model", None))
        if re.search(r"router$", path):
            return P(*lead(2), None, None)
        if re.search(r"conv_w$", path):
            return P(*lead(2), None, "model")
        if re.search(r"(conv_b|lam)$", path):
            return P(*lead(1), "model")
        return P(*((None,) * nd))       # norms, biases, scalars

    # ZeRO-3 shards over *all* data-parallel axes: on the multi-pod mesh
    # ("pod", "data", "model") the parameter dim splits over pod×data, so
    # per-device parameter memory matches what dp_axes implies (hardcoding
    # "data" left the pod axis replicated — 2× the memory it should be).
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))

    def fsdp_augment(spec: P, leaf) -> P:
        if not fsdp or leaf.ndim < 2:
            return spec
        s = list(spec) + [None] * (leaf.ndim - len(spec))
        for i, (ax, dim) in enumerate(zip(s, leaf.shape)):
            if ax is None and dim % dp_size == 0 and dim >= 1024:
                s[i] = dp[0] if len(dp) == 1 else dp
                break
        return P(*s)

    flat, treedef = jax.tree_util.tree_flatten_with_path(
        jax.tree.map(lambda x: x, _template(cfg)))
    specs = []
    for path, leaf in flat:
        sp = rule(_path_str(path), leaf)
        specs.append(fsdp_augment(sp, leaf))
    return jax.tree_util.tree_unflatten(treedef, specs)


def _template(cfg):
    """Shape template via eval_shape (no allocation)."""
    from repro.models import api
    return jax.eval_shape(
        lambda: api.init_params(cfg, jax.random.PRNGKey(0)))


def opt_specs(cfg, mesh, pspecs):
    """Optimizer state specs: moments inherit parameter specs."""
    return {"m": pspecs, "v": pspecs, "step": P()}


def cache_specs(cfg, mesh, batch: int, *, kv_mode: str = "auto"):
    """Decode-cache PartitionSpecs.

    kv_mode: "batch" shards cache on batch; "seq" shards the KV sequence
    dim over model (sequence-parallel decode via partial-softmax merge);
    "auto" picks seq when the per-dp-shard batch is < 1 (long-context,
    global_batch=1) or the arch is windowed with huge contexts.
    """
    dp = dp_axes(mesh)
    dp_size = int(np.prod([mesh.shape[a] for a in dp]))
    if kv_mode == "auto":
        kv_mode = "seq" if batch < dp_size else "batch"
    bspec = dp if batch >= dp_size else None

    if cfg.family == "ssm":
        return {"h": P(None, bspec, "model", None, None),
                "conv": P(None, bspec, None, "model")}
    if cfg.family == "hybrid":
        seq = "model" if kv_mode == "seq" else None
        out = {"periods": {
            "rec_h": P(None, None, bspec, "model"),
            "rec_conv": P(None, None, bspec, None, "model"),
            "k": P(None, bspec, seq, None, None),
            "v": P(None, bspec, seq, None, None)}}
        period = cfg.attn_period
        if cfg.n_layers % period:
            out["tail"] = {"h": P(None, bspec, "model"),
                           "conv": P(None, bspec, None, "model")}
        return out
    seq = "model" if kv_mode == "seq" else None
    if getattr(cfg, "kv_cache_layout", "bshd") == "bhsd":
        # head-major cache: shard heads over model when they divide evenly
        # (decode attention then needs no collective at all); fall back to
        # sequence sharding otherwise.
        tp = model_axis_size(mesh)
        if cfg.n_kv_heads % tp == 0:
            return {"k": P(None, bspec, "model", None, None),
                    "v": P(None, bspec, "model", None, None)}
        return {"k": P(None, bspec, None, seq, None),
                "v": P(None, bspec, None, seq, None)}
    # "bshd": (L, B, S, Hkv*hd), heads folded into the lanes
    return {"k": P(None, bspec, seq, None),
            "v": P(None, bspec, seq, None)}


def decode_kv_axis(cfg, mesh, batch: int, *, kv_mode: str = "auto"):
    """The mesh axis the decode cache's *sequence* dim is sharded over
    under ``cache_specs`` (None when the cache is not sequence-sharded).

    This is the glue between the cache placement chosen here and the
    sequence-parallel decode entry (``kernels.dispatch``'s
    ``decode_attention_sharded``): when it returns an axis name, decode
    should run the per-shard partial-(m, l, acc) kernel and merge through
    the psum form of ``core.softmax.stats_merge`` on that axis; when it
    returns None the unsharded fused kernel applies as-is.
    """
    if cfg.family in ("ssm",):
        return None
    specs = cache_specs(cfg, mesh, batch, kv_mode=kv_mode)
    if cfg.family == "hybrid":
        spec = specs["periods"]["k"]
    else:
        spec = specs["k"]
    from repro.models.transformer import cache_seq_axis
    layout = getattr(cfg, "kv_cache_layout", "bshd")
    s_ax = cache_seq_axis(layout, stacked=True)
    entry = spec[s_ax] if s_ax < len(spec) else None
    return entry


def serve_cache_sharding(cfg, mesh, seq_axis):
    """NamedSharding pytree for the slot engine's *stacked* KV-cache pool
    with the sequence dim sharded over ``seq_axis`` (every other dim
    replicated — the engine's pool batch stays local). This is the
    placement the engine's shard_map decode program keeps its carry in,
    so the pool is sharded once at allocation and never resharded on the
    hot path."""
    from repro.models.transformer import cache_seq_axis
    layout = getattr(cfg, "kv_cache_layout", "bshd")
    s_ax = cache_seq_axis(layout, stacked=True)
    sh = NamedSharding(mesh, P(*([None] * s_ax), seq_axis))
    return {"k": sh, "v": sh}


def batch_specs(cfg, mesh, kind: str):
    """Input-batch PartitionSpecs per shape kind."""
    b = batch_spec(mesh)
    if kind in ("train", "prefill"):
        specs = {"tokens": P(*b), "labels": P(*b)}
        if cfg.family in ("vlm", "audio"):
            specs["extra"] = P(*b, None, None)
        if kind == "prefill":
            specs.pop("labels")
            if cfg.family == "audio":
                specs.pop("tokens")
        return specs
    raise ValueError(kind)


def named(mesh, spec_tree):
    return jax.tree.map(
        lambda s: NamedSharding(mesh, s), spec_tree,
        is_leaf=lambda x: isinstance(x, P))
