"""One general generator of open-loop request schedules from a traffic mix.

A mix is a JSON file under ``bench/traffic/`` that holds only parameters:
the arrival process (Poisson) and the prompt and output length
distributions (lognormal or uniform, clipped). Every seed gets the same
multiset of sizes and inter-arrival gaps (stratified quantiles of the
mix's distributions); the seed decides their order and the token ids. So
the work of a run is fixed by the mix, the rate and the window, and two
seeds differ only in which request comes when.
"""

from __future__ import annotations

import math
from dataclasses import dataclass

import numpy as np

# Share of rate x seconds that is scheduled: the quantile gaps sum to a
# little under n / rate, so every scheduled request is due in the window.
FILL = 0.98


@dataclass
class Schedule:
    due: np.ndarray          # (n,) seconds after the window opens
    prompt_len: np.ndarray   # (n,) int
    max_new: np.ndarray      # (n,) int
    prompts: list            # n int32 arrays

    def __len__(self):
        return len(self.due)


def _count(rate: float, seconds: float) -> int:
    return max(1, int(math.floor(rate * seconds * FILL)))


def _quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(dist: dict, q: np.ndarray) -> np.ndarray:
    """Lengths at the quantiles ``q`` of ``dist``, clipped to its range."""
    kind = dist["dist"]
    lo, hi = int(dist["min"]), int(dist["max"])
    if kind == "lognormal":
        from statistics import NormalDist
        z = np.array([NormalDist().inv_cdf(float(x)) for x in q])
        x = float(dist["median"]) * np.exp(float(dist["sigma"]) * z)
    elif kind == "uniform":
        x = lo + q * (hi - lo + 1) - 0.5
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.rint(x), lo, hi).astype(np.int64)


def gaps(arrivals: dict, rate: float, q: np.ndarray) -> np.ndarray:
    """Inter-arrival gaps (seconds) at the quantiles ``q``: exponential of
    mean 1/rate (Poisson arrivals)."""
    if arrivals["process"] != "poisson":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    return -np.log1p(-q) / rate


def schedule(mix: dict, rate: float, seconds: float, seed: int,
             vocab: int, max_seq: int) -> Schedule:
    """The requests due in a window of ``seconds`` at ``rate`` per second.

    Every prompt + output fits ``max_seq``; a mix that cannot is refused."""
    n = _count(rate, seconds)
    rng = np.random.default_rng(int(seed))
    q = _quantiles(n)
    plen = rng.permutation(window_lengths(mix, "prompt", rate, seconds))
    nout = rng.permutation(window_lengths(mix, "output", rate, seconds))
    if (plen + nout).max() > max_seq:
        raise ValueError(f"mix exceeds max_seq {max_seq}: prompt + output "
                         f"up to {(plen + nout).max()}")
    g = rng.permutation(gaps(mix["arrivals"], rate, _quantiles(n)))
    due = np.concatenate([[0.0], np.cumsum(g[:-1])])
    prompts = [rng.integers(0, vocab, int(k), dtype=np.int32)
               for k in plen]
    return Schedule(due, plen, nout, prompts)


def window_lengths(mix: dict, which: str, rate: float,
                   seconds: float) -> np.ndarray:
    """The ``which`` ("prompt" or "output") lengths of a window's
    requests, in quantile order: the same for every seed."""
    return lengths(mix[which], _quantiles(_count(rate, seconds)))
