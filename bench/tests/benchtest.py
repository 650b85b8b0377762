"""Shared set-up of the benchmark's CPU tests: import paths, and a copy of
the benchmark's files shrunk to a size the CPU runs in seconds."""

import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
for p in (os.path.join(ROOT, "src"), os.path.join(BENCH, "reference"),
          BENCH):
    if p not in sys.path:
        sys.path.insert(0, p)

TINY_MIX = {"prompt": {"dist": "lognormal", "median": 16, "sigma": 0.5,
                       "min": 6, "max": 40},
            "output": {"dist": "uniform", "min": 3, "max": 10}}


def tiny_bench(dst, cell: str, *, gap_limit: float = 0.05):
    """A copy of the benchmark's files under ``dst`` in which ``cell``
    serves 4 slots of 64 positions, 4 requests/s of short prompts."""
    d = os.path.join(str(dst), "bench")
    shutil.copytree(BENCH, d, ignore=shutil.ignore_patterns(
        "__pycache__", "tests"))
    path = os.path.join(d, "cells", f"{cell}.json")
    c = json.load(open(path))
    c.update(rate_rps=4.0)
    c["correct"]["max_logit_gap"] = gap_limit
    json.dump(c, open(path, "w"))
    path = os.path.join(d, "configs", f"{c['config']}.json")
    conf = json.load(open(path))
    conf.update(max_batch=4, max_seq=64)
    json.dump(conf, open(path, "w"))
    path = os.path.join(d, "traffic", f"{c['traffic']}.json")
    mix = json.load(open(path))
    mix.update(TINY_MIX)
    json.dump(mix, open(path, "w"))
    return d


def tiny_run(tmp_path, cell: str, seed: int = 3000000019,
             seconds: float = 2.0, trace: bool = False):
    """One CPU run of ``cell`` at the reduced configuration."""
    import harness
    import registry
    from repro.configs import get_config
    d = tiny_bench(tmp_path, cell)
    reg = registry.Registry(d)
    c = reg.cell(cell)
    bench = registry.load_benchmark(ROOT)
    if all(w["name"] != cell for w in bench["workloads"]):
        bench["workloads"].append({"name": cell, "config": c["config"],
                                   "traffic": c["traffic"], "chips": 1})
    arch = reg.config(c["config"])["arch"]
    return harness.run_cell(cell, seed, seconds, trace,
                            t_start=time.perf_counter(), require_tpu=False,
                            reg=reg, bench=bench,
                            cfg=get_config(arch).reduced())
