"""Benchmark harness entry point: one section per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows. "us_per_call" carries the
benchmark's primary scalar (latency in us where the benchmark is a timing,
otherwise the headline metric); "derived" carries the paper target /
context.

  PYTHONPATH=src python -m benchmarks.run [--section NAME] [--with-roofline]
"""

from __future__ import annotations

import argparse
import sys
import traceback


def _emit(section, rows):
    for name, val, note in rows:
        print(f"{section}/{name},{val:.6g},{str(note).replace(',', ';')}")


def _subprocess_report(module: str):
    """Benchmarks that need a multi-device host platform require XLA_FLAGS
    set *before* jax initializes — run them in a subprocess and relay
    their rows. The child runs on CPU virtual devices only, so it can
    never contend with this process for an accelerator."""
    import os
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8")
    out = subprocess.run(
        [sys.executable, "-m", f"benchmarks.{module}"],
        capture_output=True, text=True, timeout=3600, env=env)
    if out.returncode != 0:
        raise RuntimeError(f"{module} failed:\n{out.stderr[-2000:]}")
    rows = []
    for line in out.stdout.strip().splitlines():
        if not line.startswith(f"{module}/"):
            continue
        name, val, note = line.split(",", 2)
        rows.append((name.split("/", 1)[1], float(val), note))
    return rows


def _sharded_decode_report():
    return _subprocess_report("sharded_decode")


def _collective_merge_report():
    return _subprocess_report("collective_merge")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--section", default=None)
    ap.add_argument("--skip-roofline", action="store_true",
                    help="skip the roofline table (needs dryrun artifacts)")
    args = ap.parse_args()

    from . import (snitch_model, exp_accuracy, model_accuracy,
                   softmax_speed, flashattention, e2e_models,
                   policy_sweep, serving, paged_serving, speculative)

    sections = {
        "snitch_model": snitch_model.report,       # Fig.6 + Table III
        "exp_accuracy": exp_accuracy.report,       # §V-A + Table IV
        "model_accuracy": model_accuracy.report,   # Table II
        "softmax_speed": softmax_speed.report,     # Fig.6a-c
        "flashattention": flashattention.report,   # Fig.6d-f
        "e2e_models": e2e_models.report,           # Fig.1 + Fig.8
        "policy_sweep": policy_sweep.report,       # ExecPolicy backends
        "serving": serving.report,                 # continuous batching
        "paged_serving": paged_serving.report,     # paged KV + prefix cache
        "speculative": speculative.report,         # draft/verify decode
        "sharded_decode": _sharded_decode_report,  # seq-parallel decode
        "collective_merge": _collective_merge_report,  # packed vs split
    }
    print("name,us_per_call,derived")
    failures = 0
    for name, fn in sections.items():
        if args.section and name != args.section:
            continue
        try:
            _emit(name, fn())
        except Exception:
            failures += 1
            print(f"{name},nan,ERROR", file=sys.stdout)
            traceback.print_exc()

    if not args.skip_roofline and not args.section:
        try:
            from . import roofline
            rows = roofline.build_table()
            for r in rows:
                print(f"roofline/{r['arch']}__{r['shape']},"
                      f"{max(r['compute_s'], r['memory_s'], r['collective_s']) * 1e6:.6g},"
                      f"bottleneck={r['bottleneck']};MFU={r['roofline_fraction']:.3f};"
                      f"useful={r['useful_ratio']:.2f}")
        except Exception:
            traceback.print_exc()

    if failures:
        raise SystemExit(f"{failures} benchmark sections failed")


if __name__ == "__main__":
    main()
