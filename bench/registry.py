"""Find the benchmark's pieces by name.

Each configuration, traffic mix, cell and per-layer metric lives in a
file of its own under the benchmark's directory; adding one means adding
a file (and an entry in ``BENCHMARK.json``), never editing one:

    configs/<config>.json     arch id, pool and policy, source, reference
    traffic/<mix>.json        length distributions and arrival process
    cells/<cell>.json         config, mix, rate, latency and output limits
    metrics/<metric>.py       ``read(run) -> float | None`` per-layer reader
    kernels/<kernel>.py       operations and bytes of one kernel call
    reference/<name>.py       plain float32 reference of a model family
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class Registry:
    def __init__(self, bench_dir: str = HERE):
        self.dir = bench_dir

    def _json(self, kind: str, name: str) -> dict:
        path = os.path.join(self.dir, kind, f"{name}.json")
        if not os.path.exists(path):
            raise FileNotFoundError(f"no {kind[:-1]} named {name!r} "
                                    f"({path})")
        with open(path) as fh:
            out = json.load(fh)
        out.setdefault("name", name)
        return out

    def cell(self, name: str) -> dict:
        return self._json("cells", name)

    def config(self, name: str) -> dict:
        return self._json("configs", name)

    def traffic(self, name: str) -> dict:
        return self._json("traffic", name)

    def _module(self, kind: str, name: str):
        path = os.path.join(self.dir, kind, f"{name}.py")
        if not os.path.exists(path):
            raise FileNotFoundError(f"no {kind} module named {name!r} "
                                    f"({path})")
        key = f"bench_{kind}_{name}".replace(".", "_").replace("-", "_")
        if key in sys.modules:
            return sys.modules[key]
        d = os.path.dirname(path)
        if d not in sys.path:
            sys.path.insert(0, d)
        spec = importlib.util.spec_from_file_location(key, path)
        mod = importlib.util.module_from_spec(spec)
        sys.modules[key] = mod
        spec.loader.exec_module(mod)
        return mod

    def metric(self, name: str):
        return self._module("metrics", name)

    def kernel(self, name: str):
        return self._module("kernels", name)

    def reference(self, name: str):
        return self._module("reference", name)

    def peaks(self, device_kind: str) -> dict:
        with open(os.path.join(self.dir, "peaks.json")) as fh:
            table = json.load(fh)
        if device_kind not in table["devices"]:
            raise KeyError(f"device kind {device_kind!r} is not in the "
                           f"peaks table ({sorted(table['devices'])})")
        return table["devices"][device_kind]


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as fh:
        return json.load(fh)


def metrics_for(bench: dict, section: str, cell: str) -> list[dict]:
    """The entries of ``section`` ("end_to_end" or "per_layer") that the
    cell reports: those without a ``workloads`` key, or naming it."""
    return [m for m in bench[section]
            if "workloads" not in m or cell in m["workloads"]]
