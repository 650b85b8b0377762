"""Reduce a JAX profiler trace of the traced stretch to device numbers.

The device plane (``/device:TPU:<n>``) has a line of program executions
("XLA Modules", events named ``jit_<fn>(<id>)``) and one of the ops inside
them ("XLA Ops", events named by their HLO text, ``%name = ...``). The
host plane carries the benchmark's own spans (``TraceAnnotation``):
``bench.traced`` spans the stretch, and ``server.step``,
``server.submit`` and ``gen.sleep`` say what the host was doing.

Busy time is the union of program executions, clipped to the stretch;
program and kernel times are sums of the durations of the events that
overlap it, matched by a regular expression that the metric's own file
holds.
"""

from __future__ import annotations

import glob
import re
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.traced"
HOST_SPANS = ("server.step", "server.submit", "gen.sleep")
MODULES, OPS = "XLA Modules", "XLA Ops"
# Ops that enclose other ops: left out of the list of top operations.
_ENCLOSING = re.compile(r"^%(while|conditional|call)[.0-9]* = ")


@dataclass
class Event:
    name: str
    start: int      # ns
    end: int        # ns


@dataclass
class Trace:
    window: tuple                              # (start_ns, end_ns)
    modules: list = field(default_factory=list)
    ops: list = field(default_factory=list)
    host: list = field(default_factory=list)   # benchmark spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def _inside(self, evs):
        """Events that overlap the stretch. The harness syncs the device
        before it opens the stretch and before it closes it, so a program
        that reaches past an edge is the host's and the device's clocks
        disagreeing, not work of another stretch: it counts whole."""
        a, b = self.window
        return [e for e in evs if e.end > a and e.start < b]

    def straddling(self) -> int:
        """Program executions that reach past an edge of the stretch."""
        a, b = self.window
        return sum(1 for e in self._inside(self.modules)
                   if e.start < a or e.end > b)

    def busy_intervals(self) -> list:
        a, b = self.window
        iv = sorted((max(e.start, a), min(e.end, b)) for e in self.modules
                    if e.end > a and e.start < b)
        out = []
        for s, t in iv:
            if out and s <= out[-1][1]:
                out[-1][1] = max(out[-1][1], t)
            else:
                out.append([s, t])
        return out

    @property
    def busy_s(self) -> float:
        return sum(t - s for s, t in self.busy_intervals()) * 1e-9

    def program_time(self, pattern: str):
        """(seconds, executions) of programs whose name matches."""
        rx = re.compile(pattern)
        evs = [e for e in self._inside(self.modules) if rx.search(e.name)]
        return sum(e.end - e.start for e in evs) * 1e-9, len(evs)

    def op_time(self, pattern: str):
        """(seconds, calls) of device ops whose HLO text matches."""
        rx = re.compile(pattern)
        evs = [e for e in self._inside(self.ops) if rx.search(e.name)]
        return sum(e.end - e.start for e in evs) * 1e-9, len(evs)

    def top_ops(self, n: int = 10) -> list:
        """The ``n`` device operations that took most time: [name, s]."""
        tot: dict = {}
        for e in self._inside(self.ops):
            if _ENCLOSING.match(e.name):
                continue
            key = op_label(e.name)
            tot[key] = tot.get(key, 0) + (e.end - e.start)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]

    def idle_gaps(self, n: int = 10) -> list:
        """Idle time between programs, by the host span that overlaps
        each gap most: [label, total seconds], longest first."""
        a, b = self.window
        busy = self.busy_intervals()
        edges = [a] + [x for iv in busy for x in iv] + [b]
        tot: dict = {}
        for s, t in zip(edges[0::2], edges[1::2]):
            if t <= s:
                continue
            best, label = 0, "outside benchmark spans"
            for h in self.host:
                ov = min(t, h.end) - max(s, h.start)
                if ov > best:
                    best, label = ov, h.name
            tot[label] = tot.get(label, 0) + (t - s)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:n]
        return [[k, v * 1e-9] for k, v in top]


def op_label(hlo: str) -> str:
    """``%copy.106 = bf16[32,1024,12,64]{...} copy(...)`` ->
    ``copy.106 bf16[32,1024,12,64] copy``."""
    m = re.match(r"^%(\S+) = (\S+?)(\{[^}]*\})? ([a-z_-]+)\(", hlo)
    if not m:
        return hlo[:80]
    return f"{m.group(1)} {m.group(2)} {m.group(4)}"


def load(log_dir: str, device: int = 0) -> Trace:
    """Read the newest ``.xplane.pb`` under ``log_dir``."""
    import jax

    files = sorted(glob.glob(f"{log_dir}/**/*.xplane.pb", recursive=True))
    if not files:
        raise FileNotFoundError(f"no profiler trace under {log_dir}")
    data = jax.profiler.ProfileData.from_file(files[-1])
    return from_planes(data.planes, device)


def from_planes(planes, device: int = 0) -> Trace:
    """Build a ``Trace`` from profiler planes (objects with ``name`` and
    ``lines``; lines with ``name`` and ``events``; events with ``name``,
    ``start_ns`` and ``duration_ns``)."""
    dev_name = f"/device:TPU:{device}"
    modules, ops, host, window = [], [], [], None
    for plane in planes:
        for line in plane.lines:
            if plane.name == dev_name and line.name in (MODULES, OPS):
                dst = modules if line.name == MODULES else ops
                dst.extend(Event(e.name, int(e.start_ns),
                                 int(e.start_ns + e.duration_ns))
                           for e in line.events)
            elif plane.name.startswith("/host"):
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
                    elif e.name in HOST_SPANS:
                        host.append(Event(e.name, int(e.start_ns),
                                          int(e.start_ns + e.duration_ns)))
    if window is None:
        raise ValueError(f"the trace has no {WINDOW_SPAN!r} span")
    if not modules:
        raise ValueError(f"the trace has no {MODULES!r} events on "
                         f"{dev_name}")
    tr = Trace(window, modules, ops, host)
    if not tr._inside(modules):
        raise ValueError(
            f"no program execution overlaps the {WINDOW_SPAN!r} span "
            f"({window}); device events run from {modules[0].start} to "
            f"{modules[-1].end}: the host and device clocks disagree")
    return tr
