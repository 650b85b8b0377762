"""Serving-engine benchmark: slot-level continuous batching under load.

Three workloads on the reduced GPT-2 config (the paper's serving model),
compared against a fixed-shape chunk driver with the old scheduler's
semantics (batch-wide prefill + one scalar decode position — the shape the
engine replaced):

  uniform       all prompts the same length — the scheduler generality
                must not regress the throughput the old driver got here;
  mixed_len     ragged prompt lengths — the case the old driver answered
                incorrectly; measured for tok/s + per-step tail latency;
  mixed_policy  half the requests under ``exact`` (eval traffic), half
                under ``vexp`` (bulk) in one server.

Phase-separated measurement: the blended per-workload tok/s above mixes
prefill and decode, which hides decode regressions behind prefill wins —
the ``steady_state`` section therefore times the two phases at explicit
device syncs (admit -> sync, then N decode steps -> sync) and reports
**steady-state decode tok/s** on its own. The ``sharded`` section runs
the same phase measurement through the SPMD serve loop (KV cache
sequence-sharded over 8 fake host devices, fused partial-statistics
decode with the packed single-collective merge) in a subprocess —
XLA_FLAGS must land before jax initializes.

The ``recurrent`` section serves the ssm (mamba2) and hybrid
(recurrentgemma) reduced configs through the same slot engine — the
family-agnostic DecodeState pool — on a mixed-length workload.

The ``open_loop`` section drives the engine with a Poisson arrival
process (requests arrive at ``--rate`` req/s regardless of service
progress — closed-loop workloads can never show queueing delay) and
compares the monolithic-wave scheduler against chunked prefill
(``ExecPolicy.prefill_chunk``) at the same arrivals: per-engine-tick
wall time (each tick synced, so a tick that runs a whole prefill wave
pays for it honestly), per-request TTFT and completion p50/p95. The
chunked arm's per-tick p95 must beat the monolithic arm's — one bounded
chunk per tick is the whole point. Run just this section with
``python -m benchmarks.serving --load-mode open [--rate R]``.

The ``chaos`` section serves the identical workload twice — fault-free
and threaded with a seeded ``repro.ft.FaultInjector`` at the default
chaos rates (``REPRO_FAULT_SEED`` seeds it) — and reports goodput
(tokens from cleanly-finished requests per second), tail latency and
fault/quarantine counts for both, plus their ratio. Every chaos run
ends on ``Server.assert_idle_clean``, so the benchmark doubles as a
zero-leak check under storm conditions. Run just this section with
``python -m benchmarks.serving --chaos``.

Rows carry tokens/s as the primary scalar; per-request p50/p95 completion
latency (submit -> tokens materialized, measured at the finish-time
device sync) rides in the note. Results persist to ``BENCH_serving.json``.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys
import time

import numpy as np
import jax
import jax.numpy as jnp

OUT_PATH = os.environ.get("BENCH_SERVING_PATH", "BENCH_serving.json")

N_REQUESTS = 16
MAX_NEW = 16
MAX_BATCH = 4
MAX_SEQ = 128
UNIFORM_LEN = 32
N_TIMED = 5          # median-of-N (container noise is large + asymmetric)
STEADY_STEPS = 12    # decode steps per steady-state phase measurement
OPEN_RATE = 16.0     # Poisson arrival rate (req/s) for the open-loop arm
OPEN_CHUNK = 16      # prefill chunk tokens for the chunked open-loop arm
OPEN_TIMED = 3       # open-loop runs are wall-clock long; fewer medians


def _requests(cfg, lens, groups=None):
    from repro.launch.serve import Request
    rng = np.random.default_rng(0)
    names = groups or ["default"]
    return [Request(i, rng.integers(0, cfg.vocab, (lens[i],),
                                    dtype=np.int32), MAX_NEW,
                    group=names[i % len(names)])
            for i in range(len(lens))]


def _engine_runner(cfg, params, lens, *, policy=None, policy_groups=None):
    """Warm up (compiles) and return a closure serving the workload once."""
    from repro.launch.serve import Server

    def once():
        srv = Server(cfg, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                     policy=policy, policy_groups=policy_groups)
        reqs = _requests(cfg, lens,
                         sorted(policy_groups) if policy_groups else None)
        t0 = time.perf_counter()
        srv.run(reqs)
        dt = time.perf_counter() - t0
        ntok = sum(len(r.out) for r in reqs)
        # request-level tail latency: submit -> tokens materialized, each
        # measured at a real device sync (per-step dispatch times are
        # async and would under-report).
        lat = sorted(x for g in srv._groups.values() for x in g.req_lat)
        return {
            "tok_s": ntok / dt,
            "tokens": ntok,
            "wall_s": dt,
            "p50_req_ms": 1e3 * (lat[len(lat) // 2] if lat else 0.0),
            "p95_req_ms": 1e3 * (lat[min(int(len(lat) * 0.95),
                                         len(lat) - 1)] if lat else 0.0),
        }

    once()                      # warmup: compile prefill buckets + decode
    return once


def _median(runs, key=None):
    runs = sorted(runs, key=key)
    return runs[len(runs) // 2]


def _run_engine(cfg, params, lens, **kw):
    once = _engine_runner(cfg, params, lens, **kw)
    return _median([once() for _ in range(N_TIMED)],
                   key=lambda r: r["tok_s"])


def _steady_state(cfg, params, *, policy=None, mesh=None, kv_mode="auto",
                  n_steps=STEADY_STEPS, n_timed=3):
    """Phase-separated engine measurement: prefill wall (admit -> sync)
    and steady-state decode tok/s (N full-pool decode steps between
    syncs, no admissions or finishes inside the window)."""
    from repro.launch.serve import Server, Request

    def once():
        srv = Server(cfg, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                     mesh=mesh, policy=policy, kv_mode=kv_mode)
        rng = np.random.default_rng(0)
        for i in range(MAX_BATCH):
            srv.submit(Request(i, rng.integers(
                0, cfg.vocab, (UNIFORM_LEN,), dtype=np.int32),
                max_new=n_steps + 8))       # no slot finishes mid-window
        g = srv._groups["default"]
        t0 = time.perf_counter()
        g.admit()
        jax.block_until_ready(g.last)
        t1 = time.perf_counter()
        for _ in range(n_steps):
            g.decode_once()
        jax.block_until_ready(g.last)
        t2 = time.perf_counter()
        return {"prefill_s": t1 - t0,
                "decode_tok_s": MAX_BATCH * n_steps / (t2 - t1),
                "prefill_tok_s": MAX_BATCH * UNIFORM_LEN / (t1 - t0),
                "kv_axis": srv.kv_axis}

    once()                                  # compile
    return _median([once() for _ in range(n_timed)],
                   key=lambda r: r["decode_tok_s"])


def _sharded_arm():
    """SPMD serve-loop phase measurement: runs in a subprocess with 8
    forced host devices (see __main__), comparing the sequence-sharded
    fused decode path against the single-device engine in-process."""
    from repro.configs import get_config
    from repro.launch.mesh import make_host_mesh
    from repro.models import api
    from repro.runtime import resolve_policy

    cfg = get_config("gpt2-small").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    pol = resolve_policy(cfg, env={}, kernel_backend="pallas")
    nsh = len(jax.devices())
    sharded = _steady_state(cfg, params, policy=pol,
                            mesh=make_host_mesh(1, nsh), kv_mode="seq")
    single = _steady_state(cfg, params, policy=pol,
                           mesh=make_host_mesh(1, 1))
    return {"n_shards": nsh, "merge_strategy": pol.merge_strategy,
            "sharded": sharded, "single_device": single}


def _recurrent_arm():
    """Recurrent families through the same slot engine: mixed-length
    continuous batching over the family-agnostic DecodeState pool (ssm =
    mamba2 per-layer (h, conv) snapshots; hybrid = recurrentgemma mixed
    recurrent/attention periods). Prompt lengths stay inside the hybrid
    reduced config's sliding window (its ragged admission width)."""
    from repro.configs import get_config
    from repro.models import api
    from repro.runtime import resolve_policy

    rng = np.random.default_rng(2)
    lens = [int(x) for x in rng.integers(4, 13, N_REQUESTS)]
    out = {}
    for fam, arch in (("ssm", "mamba2-1.3b"),
                      ("hybrid", "recurrentgemma-9b")):
        cfg = get_config(arch).reduced()
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        pol = resolve_policy(cfg, env={})
        res = _run_engine(cfg, params, lens, policy=pol)
        res["arch"] = arch
        out[fam] = res
    return out


def _fixed_chunk_runner(cfg, params, lens, *, policy=None):
    """The old driver's schedule (uniform lengths only): whole-batch
    prefill, then scalar-position decode for the batch-wide max_new.
    Warms up and returns a tok/s closure."""
    from repro.models import api
    pol = policy
    prefill = jax.jit(lambda p, t: api.prefill(p, cfg, {"tokens": t},
                                               policy=pol))
    decode = jax.jit(lambda p, t, c, pos: api.decode_step(p, cfg, t, c, pos,
                                                          policy=pol))
    rng = np.random.default_rng(0)
    plen = lens[0]
    assert all(n == plen for n in lens), "fixed-chunk baseline is uniform"
    prompts = rng.integers(0, cfg.vocab, (len(lens), plen)).astype(np.int32)

    def once():
        t0 = time.perf_counter()
        ntok = 0
        for i in range(0, len(lens), MAX_BATCH):
            toks = jnp.asarray(prompts[i:i + MAX_BATCH])
            b = toks.shape[0]
            logits, cache = prefill(params, toks)
            ck = api.init_cache(cfg, b, MAX_SEQ)["k"]
            ck = ck.at[:, :, :plen].set(cache["k"])
            cv = jnp.zeros_like(ck).at[:, :, :plen].set(cache["v"])
            cache = {"k": ck, "v": cv}
            tok = jnp.argmax(logits[:, -1:], -1).astype(jnp.int32)
            ntok += b
            for step in range(MAX_NEW - 1):
                logits, cache = decode(params, tok, cache,
                                       jnp.int32(plen + step))
                tok = jnp.argmax(logits, -1).astype(jnp.int32)
                ntok += b
        jax.block_until_ready(tok)
        return ntok / (time.perf_counter() - t0)

    once()
    return once


def _open_loop_runner(cfg, params, lens, arrivals, *, policy):
    """Open-loop load: requests arrive on the fixed ``arrivals`` clock
    (seconds from start) no matter how far behind the engine is — the
    arrival process both arms share, so queueing delay is comparable.

    Per-tick latency is measured at a device sync after every
    ``Server.step()``: the engine's own dispatch times are async and
    would hide a monolithic prefill wave inside a later sync. A tick
    that admits a whole prompt pays its full prefill here; a chunked
    tick pays one bounded chunk. Warms up (compiles every prefill
    bucket / the chunk program) and returns a closure."""
    from repro.launch.serve import Server, Request

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg.vocab, (n,), dtype=np.int32)
               for n in lens]

    def once():
        srv = Server(cfg, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                     policy=policy)
        reqs = [Request(i, prompts[i], MAX_NEW) for i in range(len(lens))]
        groups = list(srv._groups.values())
        step_s: list = []
        i = 0
        t0 = time.perf_counter()
        while True:
            now = time.perf_counter() - t0
            while i < len(reqs) and arrivals[i] <= now:
                srv.submit(reqs[i])
                i += 1
            if not any(g.busy for g in groups):
                if i >= len(reqs):
                    break
                # idle before the next arrival: sleep it off rather than
                # spin (empty ticks would dilute the percentiles).
                time.sleep(max(0.0, arrivals[i]
                               - (time.perf_counter() - t0)))
                continue
            ts = time.perf_counter()
            srv.step()
            jax.block_until_ready([g.last for g in groups])
            step_s.append(time.perf_counter() - ts)
        wall = time.perf_counter() - t0
        ntok = sum(len(r.out) for r in reqs)
        ttft = sorted(x for g in groups for x in g.ttft)
        lat = sorted(x for g in groups for x in g.req_lat)
        step_s.sort()

        def pct(xs, q):
            return 1e3 * xs[min(int(len(xs) * q), len(xs) - 1)] \
                if xs else 0.0

        return {
            "tok_s": ntok / wall,
            "wall_s": wall,
            "ticks": len(step_s),
            "p50_step_ms": pct(step_s, 0.50),
            "p95_step_ms": pct(step_s, 0.95),
            "p50_ttft_ms": pct(ttft, 0.50),
            "p95_ttft_ms": pct(ttft, 0.95),
            "p50_req_ms": pct(lat, 0.50),
            "p95_req_ms": pct(lat, 0.95),
        }

    once()                      # warmup: compile buckets / chunk program
    return once


def _open_loop_arm(cfg, params, *, policy, rate=OPEN_RATE,
                   chunk=OPEN_CHUNK, n_timed=OPEN_TIMED):
    """Chunked-vs-monolithic under identical Poisson arrivals. Prompt
    lengths reach deep into the cache (long prefills are what make a
    monolithic admission tick expensive); runs interleave so container
    noise hits both arms alike; median by per-tick p95."""
    import dataclasses

    rng = np.random.default_rng(5)
    lens = [int(x) for x in rng.integers(8, 97, N_REQUESTS)]
    arrivals = np.cumsum(rng.exponential(1.0 / rate, N_REQUESTS))
    pol_chunk = dataclasses.replace(policy, prefill_chunk=chunk)
    mono_once = _open_loop_runner(cfg, params, lens, arrivals,
                                  policy=policy)
    chunk_once = _open_loop_runner(cfg, params, lens, arrivals,
                                   policy=pol_chunk)
    mono_runs, chunk_runs = [], []
    for _ in range(n_timed):
        mono_runs.append(mono_once())
        chunk_runs.append(chunk_once())
    key = lambda r: r["p95_step_ms"]          # noqa: E731
    return {
        "rate_req_s": rate,
        "chunk_tokens": chunk,
        "lens": lens,
        "monolithic": _median(mono_runs, key=key),
        "chunked": _median(chunk_runs, key=key),
    }


def _chaos_arm(cfg, params, *, n_timed=OPEN_TIMED):
    """Goodput under injected faults vs fault-free on the IDENTICAL
    workload: same prompts, same engine, one arm threaded with a seeded
    FaultInjector at the default chaos rates (REPRO_FAULT_SEED seeds
    it). Goodput counts only tokens from cleanly-finished requests —
    quarantined/shed work is overhead, not progress — so the ratio row
    is the price of the faults plus the recovery machinery. Every run
    ends on ``assert_idle_clean``: the benchmark doubles as a leak
    check under storm conditions."""
    from repro.ft import FAULT_SEED_ENV, FaultInjector, default_chaos_rates
    from repro.launch.serve import Server

    seed = int(os.environ.get(FAULT_SEED_ENV, "0") or "0")
    rng = np.random.default_rng(7)
    lens = [int(x) for x in rng.integers(8, 49, N_REQUESTS)]

    def once(inj_seed):
        inj = (FaultInjector(seed=inj_seed, rates=default_chaos_rates())
               if inj_seed is not None else None)
        srv = Server(cfg, params, max_batch=MAX_BATCH, max_seq=MAX_SEQ,
                     injector=inj, degrade_groups=("default",))
        reqs = _requests(cfg, lens)
        t0 = time.perf_counter()
        srv.run(reqs)
        wall = time.perf_counter() - t0
        clean = [r for r in reqs
                 if r.finish_reason in ("max_new", "length_cap")]
        good = sum(len(r.out) for r in clean)
        lat = sorted(x for g in srv._groups.values() for x in g.req_lat)
        st = srv.stats()["default"]
        out = {
            "goodput_tok_s": good / wall,
            "good_tokens": good,
            "clean_requests": len(clean),
            "n_requests": len(reqs),
            "wall_s": wall,
            "p95_req_ms": 1e3 * (lat[min(int(len(lat) * 0.95),
                                         len(lat) - 1)] if lat else 0.0),
            "quarantined": st["quarantined"],
            "step_faults": st["step_faults"],
            "requeued": st["requeued"],
            "shed": st["shed"],
            "admit_retries": st["admit_retries"],
        }
        if inj is not None:
            out["faults_fired"] = srv.fault_stats()["injector"]["fired"]
        srv.assert_idle_clean()        # zero leaked pages/slots, or raise
        return out

    once(None)                         # warmup: compiles both paths
    key = lambda r: r["goodput_tok_s"]          # noqa: E731
    fault_free = _median([once(None) for _ in range(n_timed)], key=key)
    # nearby seeds sample different fault mixes; median by goodput
    chaos = _median([once(seed + i) for i in range(n_timed)], key=key)
    return {
        "seed": seed,
        "rates": default_chaos_rates(),
        "fault_free": fault_free,
        "chaos": chaos,
        "goodput_ratio": chaos["goodput_tok_s"]
        / max(fault_free["goodput_tok_s"], 1e-9),
    }


def run_bench() -> dict:
    from repro.configs import get_config
    from repro.models import api
    from repro.runtime import resolve_policy

    cfg = get_config("gpt2-small").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    pol = resolve_policy(cfg, env={})
    rng = np.random.default_rng(1)
    mixed = [int(x) for x in rng.integers(8, 49, N_REQUESTS)]

    # the headline comparison (slot engine vs the old fixed-shape driver
    # on the uniform workload) interleaves the two runners so container
    # noise hits both alike; median-of-N on each side.
    engine_once = _engine_runner(cfg, params, [UNIFORM_LEN] * N_REQUESTS,
                                 policy=pol)
    fixed_once = _fixed_chunk_runner(cfg, params,
                                     [UNIFORM_LEN] * N_REQUESTS, policy=pol)
    eng_runs, fixed_runs = [], []
    for _ in range(N_TIMED):
        eng_runs.append(engine_once())
        fixed_runs.append(fixed_once())
    uniform = _median(eng_runs, key=lambda r: r["tok_s"])
    fixed_tok_s = _median(fixed_runs)

    results = {
        "uniform": uniform,
        "mixed_len": _run_engine(cfg, params, mixed, policy=pol),
        "mixed_policy": _run_engine(
            cfg, params, mixed,
            policy_groups={
                "eval": resolve_policy(cfg, env={}, exp_backend="exact"),
                "bulk": resolve_policy(cfg, env={}, exp_backend="vexp"),
            }),
        "fixed_chunk_baseline": {"tok_s": fixed_tok_s},
        "steady_state": _steady_state(cfg, params, policy=pol),
        "recurrent": _recurrent_arm(),
        "open_loop": _open_loop_arm(cfg, params, policy=pol),
        "chaos": _chaos_arm(cfg, params),
    }
    # sharded serving needs a multi-device host platform: XLA_FLAGS must
    # precede jax init, so the arm runs in a subprocess on CPU virtual
    # devices (never the accelerator this process holds). Its failure
    # fails the benchmark.
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, "-m", "benchmarks.serving", "--sharded-json"],
        capture_output=True, text=True, timeout=3600, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"sharded serving arm failed:\n"
                           f"{out.stderr[-2000:]}")
    results["sharded"] = json.loads(out.stdout.strip().splitlines()[-1])
    dev = jax.devices()[0]
    return {
        "device": f"{dev.platform}:{getattr(dev, 'device_kind', '')}",
        "backend": jax.default_backend(),
        "config": {"n_requests": N_REQUESTS, "max_new": MAX_NEW,
                   "max_batch": MAX_BATCH, "max_seq": MAX_SEQ,
                   "uniform_len": UNIFORM_LEN, "mixed_lens": mixed},
        "unix_time": time.time(),
        "results": results,
    }


def report():
    """Benchmark rows + BENCH_serving.json side effect."""
    payload = run_bench()
    with open(OUT_PATH, "w") as fh:
        json.dump(payload, fh, indent=2)
    res = payload["results"]
    rows = []
    for name in ("uniform", "mixed_len", "mixed_policy"):
        r = res[name]
        rows.append((f"{name}_tok_s", r["tok_s"],
                     f"req_p50={r['p50_req_ms']:.1f}ms;"
                     f"req_p95={r['p95_req_ms']:.1f}ms"))
    base = res["fixed_chunk_baseline"]["tok_s"]
    rows.append(("fixed_chunk_baseline_tok_s", base,
                 "old fixed-shape driver schedule (uniform lengths)"))
    rows.append(("uniform_vs_fixed_chunk",
                 res["uniform"]["tok_s"] / base,
                 "slot engine / old driver throughput (>= 1 expected)"))
    ss = res["steady_state"]
    rows.append(("steady_decode_tok_s", ss["decode_tok_s"],
                 f"decode-only; prefill={ss['prefill_s'] * 1e3:.1f}ms "
                 f"({ss['prefill_tok_s']:.1f} tok/s) measured separately"))
    ol = res.get("open_loop", {})
    if ol:
        for arm in ("monolithic", "chunked"):
            r = ol[arm]
            what = (f"chunk={ol['chunk_tokens']}tok"
                    if arm == "chunked" else "whole-prompt waves")
            rows.append((f"open_{arm}_step_p95_ms", r["p95_step_ms"],
                         f"Poisson {ol['rate_req_s']:g}req/s, {what}; "
                         f"ttft_p50/p95={r['p50_ttft_ms']:.0f}/"
                         f"{r['p95_ttft_ms']:.0f}ms; "
                         f"req_p95={r['p95_req_ms']:.0f}ms; "
                         f"{r['tok_s']:.1f}tok/s"))
        rows.append(("open_step_p95_ratio",
                     ol["monolithic"]["p95_step_ms"]
                     / max(ol["chunked"]["p95_step_ms"], 1e-9),
                     "monolithic / chunked per-tick p95 (> 1 expected: "
                     "the chunk budget bounds every tick)"))
    for fam, r in res.get("recurrent", {}).items():
        rows.append((f"recurrent_{fam}_tok_s", r["tok_s"],
                     f"{r['arch']} mixed-length slot engine; "
                     f"req_p50={r['p50_req_ms']:.1f}ms;"
                     f"req_p95={r['p95_req_ms']:.1f}ms"))
    ch = res.get("chaos", {})
    if ch:
        c = ch["chaos"]
        rows.append(("chaos_goodput_tok_s", c["goodput_tok_s"],
                     f"seed={ch['seed']}; clean={c['clean_requests']}/"
                     f"{c['n_requests']} requests; fired="
                     f"{c.get('faults_fired', {})}; "
                     f"quarantined={c['quarantined']} shed={c['shed']} "
                     f"step_faults={c['step_faults']}; "
                     f"req_p95={c['p95_req_ms']:.1f}ms"))
        rows.append(("chaos_goodput_ratio", ch["goodput_ratio"],
                     f"chaos / fault-free goodput (fault-free="
                     f"{ch['fault_free']['goodput_tok_s']:.1f}tok/s, "
                     f"req_p95={ch['fault_free']['p95_req_ms']:.1f}ms)"))
    sh = res.get("sharded", {})
    if "error" not in sh and sh:
        rows.append(("sharded_decode_tok_s",
                     sh["sharded"]["decode_tok_s"],
                     f"{sh['n_shards']}-way seq-sharded SPMD serve loop "
                     f"(merge={sh['merge_strategy']}); single-device "
                     f"decode={sh['single_device']['decode_tok_s']:.1f} "
                     f"tok/s in the same subprocess"))
    else:
        rows.append(("sharded_decode_tok_s", 0.0,
                     f"unavailable: {sh.get('error', 'not run')[:120]}"))
    rows.append(("json", 0.0, f"written to {OUT_PATH}"))
    return rows


def _open_loop_main(argv):
    """``--load-mode open [--rate R] [--chunk C]``: run just the
    open-loop Poisson comparison and print its rows (no JSON write —
    the full ``report()`` refreshes BENCH_serving.json)."""
    from repro.configs import get_config
    from repro.models import api
    from repro.runtime import resolve_policy

    def _flag(name, default, cast):
        return cast(argv[argv.index(name) + 1]) \
            if name in argv else default

    rate = _flag("--rate", OPEN_RATE, float)
    chunk = _flag("--chunk", OPEN_CHUNK, int)
    cfg = get_config("gpt2-small").reduced()
    params = api.init_params(cfg, jax.random.PRNGKey(0))
    ol = _open_loop_arm(cfg, params, policy=resolve_policy(cfg, env={}),
                        rate=rate, chunk=chunk)
    for arm in ("monolithic", "chunked"):
        r = ol[arm]
        print(f"open_loop/{arm}: step p50/p95="
              f"{r['p50_step_ms']:.1f}/{r['p95_step_ms']:.1f}ms  "
              f"ttft p50/p95={r['p50_ttft_ms']:.0f}/"
              f"{r['p95_ttft_ms']:.0f}ms  "
              f"req p50/p95={r['p50_req_ms']:.0f}/"
              f"{r['p95_req_ms']:.0f}ms  {r['tok_s']:.1f}tok/s "
              f"({r['ticks']} ticks)")
    print(f"open_loop/step_p95_ratio,"
          f"{ol['monolithic']['p95_step_ms'] / max(ol['chunked']['p95_step_ms'], 1e-9):.3g},"
          f"rate={rate:g}req/s chunk={chunk}tok")


if __name__ == "__main__":
    if "--sharded-json" in sys.argv:
        # subprocess mode (parent sets XLA_FLAGS before we ever import
        # jax): print one JSON line with the sharded phase measurement.
        print(json.dumps(_sharded_arm()))
        sys.exit(0)
    if "--chaos" in sys.argv:
        # run just the chaos arm and print its rows (no JSON write —
        # the full report() refreshes BENCH_serving.json)
        from repro.configs import get_config
        from repro.models import api
        cfg = get_config("gpt2-small").reduced()
        params = api.init_params(cfg, jax.random.PRNGKey(0))
        ch = _chaos_arm(cfg, params)
        for arm in ("fault_free", "chaos"):
            r = ch[arm]
            print(f"serving/chaos_{arm},{r['goodput_tok_s']:.6g},"
                  f"clean={r['clean_requests']}/{r['n_requests']} "
                  f"req_p95={r['p95_req_ms']:.1f}ms "
                  f"fired={r.get('faults_fired', {})}")
        print(f"serving/chaos_goodput_ratio,{ch['goodput_ratio']:.6g},"
              f"seed={ch['seed']}")
        sys.exit(0)
    if "--load-mode" in sys.argv:
        mode = sys.argv[sys.argv.index("--load-mode") + 1]
        if mode != "open":
            sys.exit(f"unknown --load-mode {mode!r} (only 'open')")
        _open_loop_main(sys.argv)
        sys.exit(0)
    for name, val, note in report():
        print(f"serving/{name},{val:.6g},{note}")
