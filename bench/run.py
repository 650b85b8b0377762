#!/usr/bin/env python3
"""Run one benchmark cell once, on the accelerator it is started on.

    python bench/run.py --workload gpt2s.chat --seed 7 --seconds 45 --trace 0

Builds the cell's configuration with random weights from ``--seed``,
warms every shape its traffic can use (set-up), offers the cell's
open-loop traffic for ``--seconds``, drains, and checks what was served
against the plain reference. Progress and the numbers compared (each
beside its limit, last) go to standard error; the last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics read from a profiler trace of a steady stretch),
``device``, with ``--trace 1`` ``breakdown``, and ``checks``.

Exits nonzero, with no result, when JAX finds no TPU or fewer chips than
the cell needs, or when the system under test is not beside the
benchmark.
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


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True, help="cell name")
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from harness import NoDevice, log, run_cell
    try:
        out = run_cell(args.workload, args.seed, args.seconds,
                       bool(args.trace), t_start=T_START)
    except NoDevice as e:
        log(f"no result: {e}")
        return 3
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
