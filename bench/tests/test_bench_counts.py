"""Operations and bytes of the kernels and of a whole decode step, against
hand counts at the published shapes."""

import json
import os
from types import SimpleNamespace as NS

import pytest

import benchtest
import registry

REG = registry.Registry()


def model(name):
    conf = json.load(open(os.path.join(benchtest.BENCH, "configs",
                                       f"{name}.json")))
    return conf["model"]


def test_flash_decode_gpt2_small():
    k = REG.kernel("flash_decode")
    m = model("gpt2-small")
    ctx = [100, 300, 1024]
    # QK^T and PV: 2 * 2 * ctx * 12 heads * 64 per row
    assert k.flops(m, ctx) == 4 * 12 * 64 * 1424
    # K and V rows at bf16: 2 * ctx * 12 * 64 * 2 B; q in and out: 2 * 768 * 2
    assert k.bytes_moved(m, ctx) == 2 * 1424 * 768 * 2 + 3 * 2 * 768 * 2
    assert k.flops(m, []) == 0 and k.bytes_moved(m, []) == 0


def test_decode_step_gpt2_small():
    k = REG.kernel("decode_step.dense")
    m = model("gpt2-small")
    # per layer 4 x 768^2 (q, k, v, o) + 2 x 768 x 3072; unembed 768 x 50257
    n = 12 * (4 * 768 * 768 + 2 * 768 * 3072) + 768 * 50257
    assert k.matmul_params(m) == n == 123_532_032
    ctx = [10, 20]
    assert k.flops(m, ctx) == 2 * n * 2 + 4 * 12 * 64 * 12 * 30


def test_decode_step_counts_live_rows_only():
    """Rows that hold no request add nothing; attention grows with each
    row's own context, not with the pool's length."""
    k = REG.kernel("decode_step.dense")
    m = model("gpt2-small")
    assert k.flops(m, []) == 0
    one = k.flops(m, [1])
    assert k.flops(m, [1] * 32) == 32 * one
    assert k.flops(m, [1024]) - one == 4 * 12 * 64 * 12 * 1023


def _trace(prog_s, prog_n, op_s, op_n):
    return NS(program_time=lambda pat: (prog_s, prog_n),
              op_time=lambda pat: (op_s, op_n))


def _view(steps, trace, family, m):
    notes = {}
    return NS(trace=trace, steps=steps, model=m, family=family,
              peaks=REG.peaks("TPU v5 lite"), kernel=REG.kernel,
              note=notes.__setitem__, counts={})


def test_decode_mfu_reader():
    m = model("gpt2-small")
    steps = [[100] * 32, [101] * 32]
    view = _view(steps, _trace(0.068, 2, 0, 0), "dense", m)
    work = sum(REG.kernel("decode_step.dense").flops(m, s) for s in steps)
    got = REG.metric("decode.mfu").read(view)
    assert got == pytest.approx(100 * work / (0.068 * 197e12))
    # steps logged and programs traced must agree, or nothing is read
    view = _view(steps, _trace(0.068, 3, 0, 0), "dense", m)
    assert REG.metric("decode.mfu").read(view) is None


def test_flash_decode_roofline_reader():
    m = model("gpt2-small")
    steps = [[512] * 32]
    k = REG.kernel("flash_decode")
    t_min = max(k.flops(m, steps[0]) / 197e12,
                k.bytes_moved(m, steps[0]) / 819e9)
    view = _view(steps, _trace(0, 0, 0.004, 12), "dense", m)
    got = REG.metric("flash_decode_roofline").read(view)
    assert got == pytest.approx(100 * 12 * t_min / 0.004)
    # a trace with no kernel calls (a model without attention) reads
    # nothing
    view = _view(steps, _trace(0, 0, 0.0, 0), "dense", m)
    assert REG.metric("flash_decode_roofline").read(view) is None


def test_occupancy_reader():
    view = NS(counts={"decode_steps": 10, "decode_tokens": 160,
                      "max_batch": 32})
    assert REG.metric("sched.decode_occupancy").read(view) == 50.0
    view = NS(counts={"decode_steps": 0, "decode_tokens": 0, "max_batch": 32})
    assert REG.metric("sched.decode_occupancy").read(view) is None


def test_peaks_table():
    p = REG.peaks("TPU v5 lite")
    assert p["bf16_flops_s"] == 197e12 and p["hbm_bytes_s"] == 819e9
    with pytest.raises(KeyError):
        REG.peaks("TPU v9 imaginary")
