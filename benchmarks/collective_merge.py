"""Packed vs split partial-softmax merge microbench -> BENCH_collective_merge.json.

The sequence-parallel decode merge (ISSUE 4 tentpole) can fold the
per-shard (m, l, acc) softmax statistics two ways:

  packed   ONE all_gather of each shard's contiguous [acc | m | l] tile
           (exactly what the flash-decode kernel's packed mode emits),
           alpha-rescaled fold running shard-locally on the gathered axis;
  split    the PR-3 three-collective form: pmax (global m) + psum of the
           alpha-rescaled l + psum of the alpha-rescaled acc.

Both compute the identical associative algebra — this bench isolates the
*collective* cost by timing just the shard_map merge programs on the
serving engine's per-layer decode-statistics tile (the reduced-GPT-2 slot
pool: 4 slots x 4 KV heads x group 1 x head dim 32 — decode merges are
tiny, which is exactly why they are latency- not bandwidth-bound), swept
over shard counts {2, 4, 8} on the fake 8-device host platform
(XLA_FLAGS must land before jax initializes: run standalone or via
benchmarks.run's subprocess section).

Protocol: each timed call runs K data-dependent chained merges inside one
jitted program (amortizes dispatch; the chain keeps XLA from eliding
repeats), arms are interleaved round-robin, and the min over many rounds
is reported — collective rendezvous on the time-shared fake devices has
heavy-tailed scheduler noise that the min cuts through. The packed arm is
fed pre-packed tiles, matching the kernel's direct packed write (no
concatenate on its clock).

  PYTHONPATH=src python -m benchmarks.collective_merge
"""

from __future__ import annotations

import os

if __name__ == "__main__":                       # before any jax import
    os.environ.setdefault("XLA_FLAGS",
                          "--xla_force_host_platform_device_count=8")

import json
import time

OUT_PATH = os.environ.get("BENCH_COLLECTIVE_MERGE_PATH",
                          "BENCH_collective_merge.json")

# The slot engine's per-layer merge unit on the reduced GPT-2 serving
# config: (max_batch, Hkv, G, hd) m/l stats + (…, hd) accumulator.
SHAPE = dict(b=4, hkv=4, g=1, d=32)
SHARDS = (2, 4, 8)
K_CHAIN = 16         # merges per timed call (dispatch amortization)
N_WARMUP = 4
N_ROUNDS = 41        # interleaved min-of-N (heavy-tailed barrier noise)


def _programs(mesh, nsh, b, hkv, g, d):
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.core.softmax import (SoftmaxStats, stats_merge_collective,
                                    stats_merge_collective_packed)
    from repro.core.vexp import get_exp_fn

    exp_fn = get_exp_fn("vexp")

    def _chain(t, merge_one):
        # K data-dependent merges: feed a zero-scaled slice of each result
        # back into the next input so XLA cannot collapse the chain.
        out = jnp.zeros(t.shape[:-1] + (d,), t.dtype)

        def step(c, _):
            t2 = t + 0.0 * jnp.pad(c, [(0, 0)] * (c.ndim - 1) + [(0, 2)])
            return merge_one(t2), None

        out, _ = jax.lax.scan(step, out, None, length=K_CHAIN)
        return out

    def packed_fn(t):
        def merge_one(tile):
            stats, acc = stats_merge_collective_packed(tile, "model",
                                                       exp_fn=exp_fn)
            return acc[..., :d] / jnp.maximum(stats.l, 1e-30)

        return _chain(t[0], merge_one)

    def split_fn(t):
        def merge_one(tile):
            m, l = tile[..., d:d + 1], tile[..., d + 1:d + 2]
            stats, acc = stats_merge_collective(
                SoftmaxStats(m=m, l=l), tile[..., :d], "model",
                exp_fn=exp_fn)
            return acc / jnp.maximum(stats.l, 1e-30)

        return _chain(t[0], merge_one)

    return {name: jax.jit(jax.shard_map(fn, mesh=mesh,
                                        in_specs=(P("model"),),
                                        out_specs=P(), check_vma=False))
            for name, fn in (("packed", packed_fn), ("split", split_fn))}


def run_sweep() -> dict:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    b, hkv, g, d = (SHAPE[k] for k in ("b", "hkv", "g", "d"))
    ndev = len(jax.devices())
    records = []
    for nsh in SHARDS:
        if nsh > ndev:
            continue
        mesh = jax.make_mesh((nsh,), ("model",))
        ks = jax.random.split(jax.random.PRNGKey(nsh), 3)
        # per-shard statistics with a realistic m spread (each shard saw a
        # different slice of the scores)
        m = jax.random.normal(ks[0], (nsh, b, hkv, g, 1)) * 4.0
        l = jax.random.uniform(ks[1], (nsh, b, hkv, g, 1)) * 100.0 + 1.0
        acc = jax.random.normal(ks[2], (nsh, b, hkv, g, d)) * 30.0
        packed = jax.device_put(jnp.concatenate([acc, m, l], axis=-1),
                                NamedSharding(mesh, P("model")))
        fns = _programs(mesh, nsh, b, hkv, g, d)
        # identical algebra: the two programs must agree before timing
        err = float(jnp.abs(fns["packed"](packed)
                            - fns["split"](packed)).max())
        assert err < 1e-4, f"packed/split merge diverged: {err}"
        for fn in fns.values():
            for _ in range(N_WARMUP):
                jax.block_until_ready(fn(packed))
        best = {name: float("inf") for name in fns}
        for _ in range(N_ROUNDS):
            for name, fn in fns.items():
                t0 = time.perf_counter()
                jax.block_until_ready(fn(packed))
                best[name] = min(best[name], time.perf_counter() - t0)
        records.append({
            "n_shards": nsh,
            "packed_us": best["packed"] * 1e6 / K_CHAIN,
            "split_us": best["split"] * 1e6 / K_CHAIN,
            "speedup": best["split"] / best["packed"],
            "max_abs_delta": err,
        })
    dev = jax.devices()[0]
    return {
        "device": f"{dev.platform}:{getattr(dev, 'device_kind', '')}",
        "backend": jax.default_backend(),
        "n_devices": ndev,
        "shape": SHAPE,
        "k_chain": K_CHAIN,
        "unix_time": time.time(),
        "records": records,
    }


def report():
    """Benchmark rows + BENCH_collective_merge.json side effect."""
    payload = run_sweep()
    with open(OUT_PATH, "w") as fh:
        json.dump(payload, fh, indent=2)
    rows = []
    for r in payload["records"]:
        nsh = r["n_shards"]
        rows.append((f"shards{nsh}/packed", r["packed_us"],
                     "single all_gather of the [acc|m|l] tile"))
        rows.append((f"shards{nsh}/split", r["split_us"],
                     f"pmax + 2xpsum; packed is {r['speedup']:.2f}x"))
    rows.append(("json", 0.0, f"written to {OUT_PATH}"))
    return rows


if __name__ == "__main__":
    for name, val, note in report():
        print(f"collective_merge/{name},{val:.6g},{note}")
