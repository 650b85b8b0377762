"""The control of the correctness check: the plain reference with fp8
matrix products, put in the system's place, must read a logit gap above
the cell's limit. Measured on the chip at each cell's own size (PERF.md);
here at the published widths and vocabulary with two layers, on token
sequences of the cell's lengths, which the CPU holds."""

import glob
import json
import os

import numpy as np
import pytest

import benchtest
import correct
import registry

REG = registry.Registry()
CELLS = [json.load(open(p)) for p in
         sorted(glob.glob(os.path.join(benchtest.BENCH, "cells", "*.json")))]


@pytest.mark.parametrize("config", sorted({c["config"] for c in CELLS}))
def test_fp8_control_fails_the_limit(config):
    import jax
    conf = REG.config(config)
    spec = dict(conf["model"], n_layers=2)
    spec["vocab_padded"] = -(-spec["vocab"] // 256) * 256
    ref = REG.reference(conf["reference"])
    params = jax.jit(lambda k: ref.init_params(spec, k))(
        jax.random.PRNGKey(3))
    rng = np.random.default_rng(3)
    seqs = [(rng.integers(0, spec["vocab"], 96, dtype=np.int32),
             rng.integers(0, spec["vocab"], 160, dtype=np.int32))
            for _ in range(4)]
    own = correct.gaps(ref, params, spec, seqs[:1])
    ctl = correct.gaps(ref, params, spec, seqs, control=True)
    # the reference's own argmax reads 0 where it is served
    best = [(p, np.full(1, 0, np.int32)) for p, _ in seqs[:1]]
    assert own.shape == (160,) and correct.gaps(ref, params, spec,
                                                best).shape == (1,)
    limits = [c["correct"]["max_logit_gap"] for c in CELLS
              if c["config"] == config]
    assert ctl.max() > max(limits), (ctl.max(), limits)
