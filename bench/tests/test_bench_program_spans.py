"""The serving program writes host spans of its own (``serve.*``) into the
profiler trace beside the benchmark's. The reduction reads none of them:
every per-layer reading, the busy union, the top operations and the idle
gaps by benchmark span come out the same with them as without."""

from types import SimpleNamespace as NS

import pytest

import benchtest  # noqa: F401  (import paths)
import harness
import registry
import trace_reduce
from test_bench_trace import ev, planes

METRICS = ("sched.decode_occupancy", "prefill.wave_ms", "decode.step_ms",
           "decode.mfu", "flash_decode_roofline", "device.idle_share")


def program_spans():
    """Spans as ``repro.launch.serve`` writes them, nested as it nests
    them, with their arguments as event stats, on the benchmark's thread
    and on one of their own."""
    def span(name, start_ms, dur_ms, **args):
        e = ev(name, start_ms, dur_ms)
        e.stats = list(args.items())
        return e
    return [
        span("serve.decode.dispatch", 101, 1, live=5),
        span("serve.admit", 131, 40, rows=2, bucket=64, pool_rows=32,
             prompt_tokens=90, queue_wait_ms=310.5),
        span("serve.admit.wait", 132, 38, runahead=1),
        span("serve.finish", 430, 60, tokens=182),
        span("serve.finish.wait", 431, 58, runahead=7),
        span("serve.decode.dispatch", 90, 2, live=3),   # before the stretch
    ]


def with_program_spans():
    p = planes()
    host = p[1]
    host.lines[0].events.extend(program_spans())
    host.lines.append(NS(name="serve thread", events=program_spans()))
    return p


def view(tr):
    reg = registry.Registry()
    model = dict(reg.config("gpt2-small")["model"], n_layers=1)
    counts = {"decode_steps": 4, "waves": 1, "decode_tokens": 70,
              "max_batch": 32}
    steps = [[40, 90, 17], [41, 91, 18], [42, 92], [43, 93, 5, 6]]
    return harness.RunView(reg, counts, tr, steps, model, "dense",
                           reg.peaks("TPU v5 lite"))


def readings(p):
    tr = trace_reduce.from_planes(p)
    v = view(tr)
    reg = registry.Registry()
    return ({m: reg.metric(m).read(v) for m in METRICS},
            {"busy": tr.busy_intervals(), "top": tr.top_ops(),
             "gaps": tr.idle_gaps(), "host": [(e.name, e.start, e.end)
                                              for e in tr.host],
             "straddling": tr.straddling(), "window": tr.window})


def test_program_spans_leave_every_reading_unchanged():
    base, base_tr = readings(planes())
    assert all(v is not None for v in base.values()), base
    spans, spans_tr = readings(with_program_spans())
    assert spans == pytest.approx(base)
    assert spans_tr == base_tr


def test_program_spans_are_not_benchmark_spans():
    tr = trace_reduce.from_planes(with_program_spans())
    assert {e.name for e in tr.host} == set(trace_reduce.HOST_SPANS)
    assert not set(trace_reduce.HOST_SPANS) & {
        e.name for e in program_spans()}
