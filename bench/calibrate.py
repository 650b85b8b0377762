#!/usr/bin/env python3
"""Measurements that set a cell's numbers, made once on the chip, in one
process (programs compile once and serve every rate and seed).

    # the knee: offered rate vs what the server sustains
    python bench/calibrate.py sweep --workload gpt2s.chat --seed 1 \\
        --seconds 30 --rates 1,2,3,4
    # the readings a correctness limit is set from: the system's widest
    # logit gap on each seed, and the fp8 control's on the same sample
    python bench/calibrate.py gaps --workload gpt2s.chat --seconds 15 \\
        --seeds 11,12,13 [--control]

Each measurement prints one JSON line on standard output (and appends it
to ``--out`` when given).
"""

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
for p in (os.path.join(ROOT, "src"), os.path.join(HERE, "reference"), HERE):
    if p not in sys.path:
        sys.path.insert(0, p)
# JAX's persistent compilation cache, at a fixed path inside the checkout
# (read when JAX is imported; the system's use_compile_cache() takes it)
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT,
                                                       ".jax_compile_cache")

from harness import (Cell, CompileClock, Served, devices, end_to_end,  # noqa: E402
                     judge, pct, served_tokens, verdict)


def emit(rec: dict, out) -> None:
    line = json.dumps(rec)
    print(line, flush=True)
    if out:
        with open(out, "a") as fh:
            fh.write(line + "\n")


def window_summary(cell, w, setup_s) -> dict:
    e2e = end_to_end(w["reqs"], w["t0"], w["sched"], cell.cell["limits"],
                     setup_s)
    done, ttft, tpot = e2e.pop("_done"), e2e.pop("_ttft"), e2e.pop("_tpot")
    # a backlog that grows shows as later requests waiting longer
    half = len(w["sched"]) // 2
    first = [x for r, x in zip(done, ttft) if r.rid < half]
    second = [x for r, x in zip(done, ttft) if r.rid >= half]
    return {**e2e, "requests": len(w["reqs"]), "finished": len(done),
            "ttft_p50_ms": pct(ttft, 50) if ttft else None,
            "tpot_p50_ms": pct(tpot, 50) if tpot else None,
            "ttft_p50_first_half_ms": pct(first, 50) if first else None,
            "ttft_p50_second_half_ms": pct(second, 50) if second else None,
            "late_p95_ms": 1e3 * pct(w["late"], 95),
            "decode_steps": w["decode_steps"], "waves": w["waves"],
            "drain_s": w["wall"] - float(w["sched"].due[-1]),
            "ttft_ms": ttft, "tpot_ms": tpot}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("mode", choices=("sweep", "gaps"))
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rates", default="")
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)

    cell = Cell(args.workload)
    devices(cell, require_tpu=True)
    import jax
    from repro.runtime import use_compile_cache
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    clock = CompileClock()

    if args.mode == "sweep":
        rates = [float(r) for r in args.rates.split(",")]
        served = Served(cell, args.seed)
        served.warm(rates, args.seconds, clock)
        setup_s = time.perf_counter() - T_START
        for rate in rates:
            c0 = clock.n
            w = served.window(rate, args.seconds, args.seed)
            rec = window_summary(cell, w, setup_s)
            rec.update(mode="sweep", workload=cell.name, rate=rate,
                       seconds=args.seconds, seed=args.seed,
                       window_compiles=clock.n - c0)
            emit(rec, args.out)
        return 0

    rate = float(cell.cell["rate_rps"])
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        served = Served(cell, seed)
        if i == 0:
            served.warm([rate], args.seconds, clock)
        w = served.window(rate, args.seconds, seed)
        e2e = end_to_end(w["reqs"], w["t0"], w["sched"],
                         cell.cell["limits"], 0.0)
        done = e2e["_done"]
        seqs = served_tokens(done)
        unfinished = len(w["reqs"]) - len(done)
        del w, done, e2e
        served.close()
        res = judge(served, seqs, seed, control=args.control)
        rec = {"mode": "gaps", "workload": cell.name, "seed": seed,
               "rate": rate, "seconds": args.seconds,
               "unfinished": unfinished,
               "sample_requests": len(res[0]),
               "sample_tokens": int(sum(len(s) for _, s in res[0])),
               "max_gap": float(res[1].max()),
               "p99_gap": float(pct(res[1], 99)),
               "share_gap0": float((res[1] == 0).mean())}
        rec["correct"] = verdict(cell, rec["max_gap"], unfinished)[0]
        if args.control:
            rec.update(control_max_gap=float(res[2].max()),
                       control_p99_gap=float(pct(res[2], 99)),
                       control_share_gap0=float((res[2] == 0).mean()))
            # the control in the system's place, judged as a run is
            rec["control_correct"] = verdict(cell, rec["control_max_gap"],
                                             0)[0]
        rec["seconds_total"] = time.perf_counter() - t0
        emit(rec, args.out)
        del served
    return 0


if __name__ == "__main__":
    sys.exit(main())
