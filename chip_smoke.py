#!/usr/bin/env python3
"""Smoke run of the serving main path on TPU, through the user entry points.

With no arguments (one chip): full-width gpt2-small (12 layers, d_model
768, 12 heads of 64, vocab 50257, random weights from ``--seed``) is
served by ``repro.launch.serve.Server`` with three policy groups (exact,
vexp, vexp_hw), all on the Pallas kernels: 12 requests with prompts of 64
to 512 tokens and 32 new tokens each, once on the contiguous KV pool and
once on the paged pool. Then one decode step's logits from the Pallas
flash-decode kernel are compared with the reference backend on the same
cache.

``--chips 4``: only the sequence-sharded decode (``kv_mode="seq"`` on a
(1, 4) mesh, packed merge) and the one-chip server on ``jax.devices()[0]``
it is compared with: greedy tokens, one decode step's logits, and the KV
pool's placement over the four devices.

Everything runs in this one process. The last line of standard output is
one JSON object, printed only when every phase passed:
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
Any failure, or a JAX that finds no TPU, exits nonzero without it.

    python chip_smoke.py
    python chip_smoke.py --chips 4
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.mesh import make_host_mesh  # noqa: E402
from repro.launch.serve import Request, Server  # noqa: E402
from repro.models import api  # noqa: E402
from repro.runtime import resolve_policy, use_compile_cache  # noqa: E402

ARCH = "gpt2-small"
MAX_BATCH, MAX_SEQ = 8, 1024
N_REQUESTS, MAX_NEW = 12, 32
PROMPT_MIN, PROMPT_MAX = 64, 512
EXP_BACKENDS = ("exact", "vexp", "vexp_hw")
# Pallas flash-decode feeds the MXU bf16 queries against the bf16 cache;
# the reference decode upcasts both to f32. That rounding moves the
# logits by under 1% of their largest magnitude; a mask that drops the
# newest key moved them by about 20% (reduced config, interpret mode).
LOGITS_RTOL = 2e-2


class CompileClock:
    """Seconds XLA spent compiling, from JAX's own monitoring events
    (tracing and lowering, which nest, stay in the wall time)."""

    def __init__(self):
        self.total = 0.0
        jax.monitoring.register_event_duration_secs_listener(self)

    def __call__(self, event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.total += duration


def log(msg):
    print(f"[smoke] {msg}", flush=True)


def check(cond, msg):
    if not cond:
        raise RuntimeError(msg)


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use")


def pallas_policies(cfg, exps):
    """One pallas policy per exp backend, resolved through the process
    environment like any entry point (so REPRO_* overrides apply)."""
    pols = {e: resolve_policy(cfg, exp_backend=e, kernel_backend="pallas")
            for e in exps}
    for name, pol in pols.items():
        check(pol.kernel_backend == "pallas",
              f"group {name} resolved to {pol.kernel_backend}")
        check(not pol.interpret_resolved(),
              f"group {name} would run the Pallas kernels in interpret mode")
    return pols


def make_requests(cfg, groups, seed):
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, N_REQUESTS)
    return [Request(i, rng.integers(0, cfg.vocab, (int(lens[i]),),
                                    dtype=np.int32),
                    MAX_NEW, group=groups[i % len(groups)])
            for i in range(N_REQUESTS)]


def timed(clock, fn):
    c0, t0 = clock.total, time.perf_counter()
    out = fn()
    return out, clock.total - c0, time.perf_counter() - t0


def serve_phase(name, clock, cfg, params, pols, seed, **server_kw):
    """Serve the request mix through ``Server``; every request must finish
    with all its tokens. Returns {rid: tokens} and the server."""
    def run():
        srv = Server(cfg, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                     policy=next(iter(pols.values())), policy_groups=pols,
                     **server_kw)
        reqs = srv.run(make_requests(cfg, sorted(pols), seed))
        return srv, reqs

    (srv, reqs), comp, wall = timed(clock, run)
    bad = [(r.rid, r.finish_reason, len(r.out)) for r in reqs
           if r.finish_reason != "max_new" or len(r.out) != MAX_NEW]
    check(not bad, f"{name}: unfinished requests (rid, reason, n): {bad}")
    stats = srv.stats()
    for g in sorted(pols):
        s = stats[g]
        log(f"{name} group {g}: prefill attention {s['prefill_attention']}"
            f", decode attention {s['decode_attention']}, "
            f"{s['decode_steps']} decode steps, "
            f"{s['admit_waves']} prefill waves")
    ntok = sum(len(r.out) for r in reqs)
    log(f"{name}: {len(reqs)}/{len(reqs)} requests finished (max_new), "
        f"{ntok} tokens; compile {comp:.3f} s, wall {wall:.3f} s, "
        f"peak_bytes_in_use {peak_bytes()}")
    return {r.rid: list(r.out) for r in reqs}, srv


def prompt_cache(cfg, params, pol, seed):
    """A decode-ready cache for MAX_BATCH ragged prompts, prefilled under
    ``pol`` and padded to MAX_SEQ positions. Returns (next tokens,
    cache, positions)."""
    rng = np.random.default_rng(seed + 1)
    plens = rng.integers(PROMPT_MIN, PROMPT_MAX + 1, MAX_BATCH)
    toks = np.zeros((MAX_BATCH, PROMPT_MAX), np.int32)
    for i, n in enumerate(plens):
        toks[i, :n] = rng.integers(0, cfg.vocab, (n,))
    logits, cache = jax.jit(lambda p, t, n: api.prefill(
        p, cfg, {"tokens": t, "prompt_len": n}, policy=pol))(
        params, jnp.asarray(toks), jnp.asarray(plens, jnp.int32))
    ax = api.cache_seq_axis(cfg.kv_cache_layout)
    widths = [(0, 0)] * cache["k"].ndim
    widths[ax] = (0, MAX_SEQ - PROMPT_MAX)
    cache = jax.tree.map(lambda c: jnp.pad(c, widths), cache)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)          # (B, 1)
    return tok, cache, jnp.asarray(plens, jnp.int32)


def compare_logits(name, cfg, got, want):
    """Compare over the real vocab: the padded tail is -1e30 in both."""
    got = np.asarray(got, np.float32)[:, :cfg.vocab]
    want = np.asarray(want, np.float32)[:, :cfg.vocab]
    check(np.isfinite(got).all(), f"{name}: non-finite logits")
    err = float(np.abs(got - want).max())
    scale = float(np.abs(want).max())
    agree = int((got.argmax(-1) == want.argmax(-1)).sum())
    log(f"{name}: max abs logits error {err:.6g} (scale {scale:.6g}, "
        f"limit {LOGITS_RTOL * scale:.6g}); greedy tokens agree "
        f"{agree}/{got.shape[0]}")
    check(err <= LOGITS_RTOL * scale,
          f"{name}: logits error {err} above {LOGITS_RTOL} x {scale}")


def decode_logits_phase(clock, cfg, params, pols, seed):
    """One decode step, Pallas flash-decode vs kernel_backend="reference",
    on the same cache, per exp backend."""
    def run():
        step = jax.jit(lambda p, t, c, pos, pol: api.decode_step(
            p, cfg, t, c, pos, policy=pol)[0][:, 0], static_argnums=4)
        ref0 = pols["exact"].replace(kernel_backend="reference")
        tok, cache, pos = prompt_cache(cfg, params, ref0, seed)
        for exp, pol in pols.items():
            ref = pol.replace(kernel_backend="reference")
            compare_logits(f"decode logits {exp} pallas vs reference", cfg,
                           step(params, tok, cache, pos, pol),
                           step(params, tok, cache, pos, ref))

    _, comp, wall = timed(clock, run)
    log(f"decode logits: compile {comp:.3f} s, wall {wall:.3f} s")


def one_chip(clock, cfg, params, seed):
    pols = pallas_policies(cfg, EXP_BACKENDS)
    contiguous, _ = serve_phase("contiguous", clock, cfg, params, pols,
                                seed)
    paged, _ = serve_phase("paged", clock, cfg, params, pols, seed,
                           paged=True)
    same = sum(contiguous[r] == paged[r] for r in contiguous)
    log(f"paged vs contiguous: {same}/{len(contiguous)} requests with "
        f"identical greedy tokens")
    decode_logits_phase(clock, cfg, params, pols, seed)


def four_chips(clock, cfg, params, seed):
    """Sequence-sharded serving over four chips against one chip."""
    from jax.sharding import PartitionSpec as P
    from repro.distributed.sharding import serve_cache_sharding
    from repro.models.transformer import decode_step_sharded

    check(len(jax.devices()) == 4,
          f"--chips 4 needs 4 devices, JAX sees {len(jax.devices())}")
    pols = pallas_policies(cfg, ("vexp",))
    pol = pols["vexp"]
    check(pol.merge_strategy == "packed",
          f"merge strategy {pol.merge_strategy}, expected packed")
    mesh = make_host_mesh(1, 4)
    sharded, srv = serve_phase("sharded", clock, cfg, params, pols, seed,
                               mesh=mesh, kv_mode="seq")
    check(srv.kv_axis == "model", f"KV pool not sequence-sharded "
          f"(kv_axis={srv.kv_axis})")
    pool = srv._groups["vexp"].state.data["k"]
    spread = pool.sharding.device_set
    seq = api.cache_seq_axis(cfg.kv_cache_layout)
    local = {s.data.shape[seq] for s in pool.addressable_shards}
    log(f"sharded KV pool: {pool.shape} over {len(spread)} devices, "
        f"{local} positions per shard")
    check(len(spread) == 4 and local == {MAX_SEQ // 4},
          "the KV pool is not spread over the four devices")
    single, _ = serve_phase("one chip", clock, cfg, params, pols, seed,
                            mesh=make_host_mesh(1, 1))
    same = sum(sharded[r] == single[r] for r in single)
    log(f"sharded vs one chip: {same}/{len(single)} requests with "
        f"identical greedy tokens")

    def run():
        tok, cache, pos = prompt_cache(
            cfg, params, pol.replace(kernel_backend="reference"), seed)
        cshard = serve_cache_sharding(cfg, mesh, "model")
        cspec = {k: s.spec for k, s in cshard.items()}
        sharded_step = jax.jit(jax.shard_map(
            lambda p, t, c, n: decode_step_sharded(
                p, cfg, t, c, n, policy=pol, seq_axis="model")[0][:, 0],
            mesh=mesh, in_specs=(P(), P(), cspec, P()), out_specs=P(),
            check_vma=False))
        step = jax.jit(lambda p, t, c, n: api.decode_step(
            p, cfg, t, c, n, policy=pol)[0][:, 0])
        compare_logits("decode logits sharded vs one chip", cfg,
                       sharded_step(params, tok,
                                    jax.device_put(cache, cshard), pos),
                       step(params, tok, cache, pos))

    _, comp, wall = timed(clock, run)
    log(f"sharded decode logits: compile {comp:.3f} s, wall {wall:.3f} s")


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="1: serve on one chip (default); 4: only the "
                         "sequence-sharded decode against one chip")
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the random weights and requests")
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        sys.exit(f"[smoke] JAX finds no TPU (platform {dev.platform!r})")
    log(f"device {dev.device_kind}, {len(jax.devices())} devices, "
        f"jax {jax.__version__}")
    log(f"compile cache: {use_compile_cache()}")
    clock = CompileClock()
    t0 = time.perf_counter()
    cfg = get_config(ARCH)
    params = jax.block_until_ready(
        api.init_params(cfg, jax.random.PRNGKey(args.seed)))
    log(f"{ARCH}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads of {cfg.hd}, vocab {cfg.vocab}, "
        f"KV layout {cfg.kv_cache_layout}; weights in "
        f"{time.perf_counter() - t0:.3f} s")
    if args.chips == 1:
        one_chip(clock, cfg, params, args.seed)
    else:
        four_chips(clock, cfg, params, args.seed)
    log(f"total: compile {clock.total:.3f} s, wall "
        f"{time.perf_counter() - t0:.3f} s, peak_bytes_in_use "
        f"{peak_bytes()}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)


if __name__ == "__main__":
    main()
