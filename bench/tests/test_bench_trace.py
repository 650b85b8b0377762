"""The reduction from a profiler trace to device numbers, on a small
synthetic trace laid out as the TPU's profiler lays it out."""

from types import SimpleNamespace as NS

import pytest

import benchtest  # noqa: F401  (import paths)
import registry
import trace_reduce

MS = 1_000_000


def ev(name, start_ms, dur_ms):
    return NS(name=name, start_ns=start_ms * MS, duration_ns=dur_ms * MS)


def planes():
    dec = "jit_decode_fn(123)"
    pre = "jit_prefill_fn(456)"
    kern = ("%decode_attention_kernel.6 = bf16[32,12,1,128]{3,2,1,0} "
            "custom-call(s32[32]{0} %a, s32[1]{0} %b)")
    copy = ("%copy.106 = bf16[32,1024,12,64]{1,3,2,0:T(8,128)} "
            "copy(bf16[32,1024,12,64]{3,2,1,0} %fusion.193)")
    loop = "%while.2 = (s32[], bf16[32,1,768]) while(%tuple), body=%b"
    device = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Modules", events=[
            ev(pre, 0, 10),       # before the stretch: not counted
            ev(dec, 100, 30), ev(dec, 140, 30), ev(pre, 180, 20),
            ev(dec, 200, 30),     # touches the previous one: one interval
            ev(dec, 400, 30)]),   # ends at 430, the stretch ends at 500
        NS(name="XLA Ops", events=[
            ev(loop, 100, 30), ev(kern, 101, 2), ev(kern, 104, 2),
            ev(copy, 106, 5), ev(kern, 141, 2), ev(kern, 144, 2),
            ev(copy, 146, 5), ev(copy, 201, 5)]),
        NS(name="Async XLA Ops", events=[ev("%copy-start.1", 100, 300)]),
    ])
    host = NS(name="/host:CPU", lines=[NS(name="python", events=[
        ev("bench.traced", 100, 400),
        ev("server.step", 100, 75),
        ev("gen.sleep", 235, 160),
        ev("server.submit", 432, 1),
        ev("server.step", 433, 60),
    ])])
    other = NS(name="/device:TPU:1", lines=[NS(name="XLA Modules", events=[
        ev(dec, 100, 399)])])
    return [device, host, other]


@pytest.fixture
def tr():
    return trace_reduce.from_planes(planes())


def test_window_and_busy_union(tr):
    assert tr.window_s == pytest.approx(0.4)
    # [100,130] [140,170] [180,230] [400,430] -> 140 ms busy
    assert tr.busy_intervals() == [[100 * MS, 130 * MS], [140 * MS, 170 * MS],
                                   [180 * MS, 230 * MS], [400 * MS, 430 * MS]]
    assert tr.busy_s == pytest.approx(0.14)
    assert tr.straddling() == 0


def test_program_past_an_edge_counts_whole():
    """The device is synced before the stretch opens and before it
    closes, so a program reaching past an edge is clock skew between the
    host's span and the device's events: its whole time counts, and busy
    time stays clipped to the stretch."""
    p = planes()
    mods = p[0].lines[0].events
    mods[1] = ev("jit_decode_fn(123)", 99, 30)       # opens 1 ms early
    mods[-1] = ev("jit_decode_fn(123)", 480, 30)     # closes 10 ms late
    tr = trace_reduce.from_planes(p)
    assert tr.program_time(r"^jit_decode_fn\(") == (pytest.approx(0.12), 4)
    assert tr.straddling() == 2
    assert tr.busy_s == pytest.approx(0.029 + 0.03 + 0.05 + 0.02)
    assert tr.busy_s <= tr.window_s


def test_idle_share_reader(tr):
    reg = registry.Registry()
    view = NS(trace=tr)
    idle = reg.metric("device.idle_share").read(view)
    assert idle == pytest.approx(100 * (1 - 0.14 / 0.4))


def test_program_and_kernel_times(tr):
    assert tr.program_time(r"^jit_decode_fn\(") == (pytest.approx(0.12), 4)
    assert tr.program_time(r"^jit_prefill_fn\(") == (pytest.approx(0.02), 1)
    kern = registry.Registry().metric("flash_decode_roofline").KERNEL
    assert tr.op_time(kern) == (pytest.approx(0.008), 4)


def test_step_readers(tr):
    reg = registry.Registry()
    view = NS(trace=tr)
    assert reg.metric("decode.step_ms").read(view) == pytest.approx(30.0)
    assert reg.metric("prefill.wave_ms").read(view) == pytest.approx(20.0)


def test_top_ops_leave_out_enclosing_loops(tr):
    top = tr.top_ops()
    names = [n for n, _ in top]
    assert names[0] == "copy.106 bf16[32,1024,12,64] copy"
    assert top[0][1] == pytest.approx(0.015)
    assert not any(n.startswith("while") for n in names)
    assert any(n.startswith("decode_attention_kernel.6") for n in names)


def test_idle_gaps_labelled_by_host_span(tr):
    gaps = dict(tr.idle_gaps())
    # 130-140 and 170-180 fall in server.step; 230-400 mostly gen.sleep;
    # 430-500 mostly the second server.step
    assert gaps["server.step"] == pytest.approx(0.02 + 0.07)
    assert gaps["gen.sleep"] == pytest.approx(0.17)
    assert sum(gaps.values()) == pytest.approx(0.4 - 0.14)


def test_trace_without_stretch_is_refused():
    p = planes()
    p[1].lines[0].events = p[1].lines[0].events[1:]
    with pytest.raises(ValueError):
        trace_reduce.from_planes(p)


def test_op_label():
    assert trace_reduce.op_label(
        "%fusion.12 = f32[32,50432]{1,0:T(8,128)} fusion(%a), kind=kLoop"
    ) == "fusion.12 f32[32,50432] fusion"
