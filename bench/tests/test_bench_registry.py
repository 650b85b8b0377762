"""The benchmark is driven by data: a configuration, traffic mix, cell or
per-layer metric is a file of its own, found by its name."""

import hashlib
import json
import os
import shutil
from types import SimpleNamespace as NS

import pytest

import benchtest
import harness
import registry
import traffic


def _hashes(d):
    out = {}
    for root, _, files in os.walk(d):
        for f in files:
            p = os.path.join(root, f)
            out[os.path.relpath(p, d)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_new_files_are_found_without_editing_any(tmp_path):
    d = tmp_path / "bench"
    shutil.copytree(benchtest.BENCH, d,
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    before = _hashes(d)
    conf = json.load(open(d / "configs" / "gpt2-small.json"))
    conf["max_batch"] = 8
    (d / "configs" / "gpt2-small.b8.json").write_text(json.dumps(conf))
    mix = json.load(open(d / "traffic" / "chat.json"))
    mix["output"] = {"dist": "uniform", "min": 8, "max": 32}
    (d / "traffic" / "chat.short.json").write_text(json.dumps(mix))
    (d / "cells" / "gpt2s.chat.short.json").write_text(json.dumps({
        "config": "gpt2-small.b8", "traffic": "chat.short",
        "rate_rps": 2.0, "limits": {"ttft_ms": 500.0, "tpot_ms": 50.0},
        "correct": {"max_logit_gap": 0.1}}))
    (d / "metrics" / "sched.steps.py").write_text(
        "def read(run):\n    return float(run.counts['decode_steps'])\n")

    reg = registry.Registry(str(d))
    bench = registry.load_benchmark(benchtest.ROOT)
    bench["workloads"].append({"name": "gpt2s.chat.short",
                               "config": "gpt2-small.b8",
                               "traffic": "chat.short", "chips": 1,
                               "why": "short answers"})
    bench["per_layer"].append({"name": "sched.steps", "unit": "1",
                               "better": "higher", "source": "program_counter",
                               "layer": "scheduler", "moves": "ttft_p95_ms",
                               "workloads": ["gpt2s.chat.short"]})
    cell = harness.Cell("gpt2s.chat.short", reg, bench)
    assert cell.max_batch == 8 and cell.conf["arch"] == "gpt2-small"
    s = traffic.schedule(cell.mix, 2.0, 20.0, 1, 50257, 1024)
    assert len(s) == int(2.0 * 20.0 * traffic.FILL)
    assert s.max_new.min() >= 8 and s.max_new.max() <= 32
    names = [m["name"] for m in
             registry.metrics_for(bench, "per_layer", "gpt2s.chat.short")]
    assert names == ["sched.steps"]
    view = NS(counts={"decode_steps": 42})
    assert reg.metric("sched.steps").read(view) == 42.0
    # nothing that was there changed
    after = _hashes(d)
    assert {k: after[k] for k in before} == before


def test_unknown_names_are_refused(tmp_path):
    reg = registry.Registry()
    with pytest.raises(FileNotFoundError):
        reg.cell("no.such.cell")
    with pytest.raises(FileNotFoundError):
        reg.metric("no.such.metric")
    with pytest.raises(KeyError):
        harness.Cell("gpt2s.chat", reg,
                     {"workloads": [], "per_layer": [], "end_to_end": []})


BENCH = registry.load_benchmark(benchtest.ROOT)


@pytest.mark.parametrize("w", BENCH["workloads"], ids=lambda w: w["name"])
def test_benchmark_cells_have_their_files(w):
    reg = registry.Registry()
    cell = reg.cell(w["name"])
    assert (cell["config"], cell["traffic"]) == (w["config"], w["traffic"])
    conf = reg.config(w["config"])
    reg.traffic(w["traffic"])
    reg.reference(conf["reference"])
    entry = next(c for c in BENCH["configs"] if c["name"] == w["config"])
    assert entry["file"] == f"bench/configs/{w['config']}.json"
    assert entry["reduced"] == conf["reduced"]
    assert cell["limits"]["ttft_ms"] > 0 and cell["limits"]["tpot_ms"] > 0
    assert cell["correct"]["max_logit_gap"] > 0
    for section in ("end_to_end", "per_layer"):
        assert registry.metrics_for(BENCH, section, w["name"])


@pytest.mark.parametrize("m", BENCH["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metrics_have_readers(m):
    assert callable(registry.Registry().metric(m["name"]).read)
    assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}
