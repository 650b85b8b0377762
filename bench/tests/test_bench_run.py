"""Whole runs of each cell on the CPU at a reduced size, and the faults
the correctness check must catch in each."""

import jax
import jax.numpy as jnp
import pytest

import benchtest
from repro.models.decode_state import DecodeState

CELLS = ["gpt2s.chat"]


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(tmp_path, cell):
    out = benchtest.tiny_run(tmp_path, cell)
    assert out["correct"] is True, out["checks"]
    assert out["failed"] == 0 and out["attempted"] > 0
    assert set(out["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                   "setup_s"}
    assert all(m["value"] > 0 for m in out["metrics"].values())
    assert list(out)[-1] == "checks"
    assert out["device"]["platform"] == "cpu"


def altered_token(monkeypatch):
    real = DecodeState.step

    def step(self, last, live):
        return (real(self, last, live) + 1) % self.cfg.vocab
    monkeypatch.setattr(DecodeState, "step", step)


def frozen_state(monkeypatch):
    real = DecodeState.step

    def step(self, last, live):
        keep = jax.tree.map(jnp.copy, (self.data, self.pos_dev))
        nxt = real(self, last, live)
        self.data, self.pos_dev = keep
        return nxt
    monkeypatch.setattr(DecodeState, "step", step)


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("fault", [altered_token, frozen_state])
def test_fault_is_not_correct(tmp_path, monkeypatch, fault, cell):
    fault(monkeypatch)
    out = benchtest.tiny_run(tmp_path, cell)
    assert out["correct"] is False
    assert out["checks"]["max_logit_gap"]["value"] > \
        out["checks"]["max_logit_gap"]["limit"]
