"""The serving loop's own measurement: the queue-exit stamp
``Request.t_admit``, the run-ahead counter ``_Group.runahead`` and the
profiler spans (``serve.admit``, ``serve.admit.wait``,
``serve.decode.dispatch``, ``serve.finish``, ``serve.finish.wait``) with
their arguments, on a reduced model on the CPU."""

import glob

import numpy as np
import pytest
import jax

from repro.configs import get_config
from repro.models import api
from repro.launch.serve import Server, Request

SPANS = {
    "serve.admit": {"rows", "bucket", "pool_rows", "prompt_tokens",
                    "queue_wait_ms"},
    "serve.admit.wait": {"runahead"},
    "serve.decode.dispatch": {"live"},
    "serve.finish": {"tokens"},
    "serve.finish.wait": {"runahead"},
}


@pytest.fixture(scope="module")
def cfg():
    return get_config("gpt2-small").reduced()


@pytest.fixture(scope="module")
def params(cfg):
    return api.init_params(cfg, jax.random.PRNGKey(0))


def _requests(cfg, lens, max_new, seed=0):
    rng = np.random.default_rng(seed)
    return [Request(i, rng.integers(0, cfg.vocab, (n,), dtype=np.int32), m)
            for i, (n, m) in enumerate(zip(lens, max_new))]


def _server(cfg, params, max_batch=2):
    return Server(cfg, params, max_batch=max_batch, max_seq=64)


def test_stamps_ordered(cfg, params):
    """Every finished request left the queue after it was submitted and
    before its first token; requests queued behind a full pool wait."""
    srv = _server(cfg, params)
    reqs = _requests(cfg, (5, 9, 7, 12, 4), (3, 6, 2, 4, 5))
    srv.run(reqs)
    for r in reqs:
        assert r.finish_reason == "max_new"
        assert 0 < r.t_submit <= r.t_admit <= r.t_first <= r.t_done, r.rid
    # two slots, five requests: the later ones queued behind the first two
    assert max(r.t_admit - r.t_submit for r in reqs[2:]) > 0


def test_t_admit_cleared_on_requeue(cfg, params):
    """A step-fault victim goes back on the queue with its stamps cleared
    and is stamped again when it is re-admitted."""
    srv = _server(cfg, params)
    g = srv._groups["default"]
    reqs = _requests(cfg, (5, 7), (6, 6))
    for r in reqs:
        srv.submit(r)
    srv.step()
    first_admit = {r.rid: r.t_admit for r in reqs}
    assert all(first_admit.values())
    g._recover_step_fault()
    assert all(r.t_admit == 0.0 and r.t_first == 0.0 for r in reqs)
    while srv.step():
        pass
    for r in reqs:
        assert r.finish_reason == "max_new"
        assert first_admit[r.rid] < r.t_admit <= r.t_first <= r.t_done


def test_runahead_counts_dispatches(cfg, params):
    """``runahead`` rises by one per decode dispatch and is 0 after every
    host sync: an admission wave's prefill wait or a finish gather."""
    srv = _server(cfg, params)
    g = srv._groups["default"]
    real_step = g.state.step
    dispatched = []

    def counted(last, live):
        dispatched.append(1)
        return real_step(last, live)

    g.state.step = counted
    reqs = _requests(cfg, (5, 9, 7), (2, 12, 4))
    for r in reqs:
        srv.submit(r)
    seen_max, busy = 0, True
    while busy:
        r0, d0, w0 = g.runahead, len(dispatched), len(g.admit_s)
        f0 = sum(1 for r in reqs if r.t_done)
        busy = srv.step()
        steps = len(dispatched) - d0
        assert steps <= 1
        if sum(1 for r in reqs if r.t_done) > f0:
            # decode_once finishes requests after its dispatch
            assert g.runahead == 0
        elif len(g.admit_s) > w0:
            assert g.runahead == steps
        else:
            assert g.runahead == r0 + steps
        seen_max = max(seen_max, g.runahead)
    assert len(dispatched) == g.decode_steps
    # the long request decodes alone for several ticks with no sync
    assert seen_max >= 3
    assert g.runahead == 0


def _host_spans(log_dir):
    """[(name, start_ns, end_ns, {arg: value})] of the ``serve.*`` host
    events in the profile written under ``log_dir``."""
    files = glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True)
    assert len(files) == 1, files
    data = jax.profiler.ProfileData.from_file(files[0])
    out = []
    for plane in data.planes:
        if not plane.name.startswith("/host"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("serve."):
                    s = int(e.start_ns)
                    out.append((e.name, s, s + int(e.duration_ns),
                                dict(e.stats)))
    return out


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


def test_profile_holds_spans_with_arguments(cfg, params, tmp_path):
    """A CPU profile of a few ticks holds the five spans with their
    arguments; the waits nest in their spans and the arguments agree with
    the server's own state."""
    srv = _server(cfg, params, max_batch=4)
    srv.run(_requests(cfg, (6,), (3,)))   # compile outside the profile
    reqs = _requests(cfg, (5, 7, 6), (3, 5, 4), seed=1)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for r in reqs:
            srv.submit(r)
        while srv.step():
            pass
    finally:
        jax.profiler.stop_trace()
    spans = _host_spans(str(tmp_path))
    by = {}
    for s in spans:
        by.setdefault(s[0], []).append(s)
    assert set(by) == set(SPANS), sorted(by)
    for name, args in SPANS.items():
        for s in by[name]:
            assert set(s[3]) == args, (name, s[3])
    # one wave: three prompts of the 8-token bucket in a pool of four
    (admit,) = by["serve.admit"]
    assert admit[3]["rows"] == 3 and admit[3]["pool_rows"] == 4
    assert admit[3]["prompt_tokens"] == 18 and admit[3]["bucket"] == 8
    assert admit[3]["queue_wait_ms"] == pytest.approx(
        1e3 * sum(r.t_admit - r.t_submit for r in reqs), rel=1e-6)
    (wait,) = by["serve.admit.wait"]
    assert _inside(wait, admit)
    # every finish gathers once, inside its span
    assert len(by["serve.finish"]) == len(reqs)
    assert len(by["serve.finish.wait"]) == len(by["serve.finish"])
    for w in by["serve.finish.wait"]:
        assert any(_inside(w, f) for f in by["serve.finish"])
    toks = sorted(s[3]["tokens"] for s in by["serve.finish"])
    assert toks == sorted(r.max_new for r in reqs)
    # each decode dispatch names its live rows; a wait reads the
    # dispatches since the wait before it
    lives = [s[3]["live"] for s in by["serve.decode.dispatch"]]
    assert lives[0] == 3 and all(1 <= n <= 3 for n in lives)
    n = 0
    for s in sorted(spans, key=lambda s: s[1]):
        if s[0] == "serve.decode.dispatch":
            n += 1
        elif s[0].endswith(".wait"):
            assert s[3]["runahead"] == n, s
            n = 0
