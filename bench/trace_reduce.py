"""Reduces a profiler trace of one run's window to device times.

The trace is the ``.xplane.pb`` that ``jax.profiler`` writes. Device
planes are named ``/device:TPU:<n>``; on each, the line ``XLA Ops``
holds one event per operation run and ``XLA Modules`` one per compiled
program run (``jit__decode(…)``). The host plane holds the benchmark's
own ``TraceAnnotation`` spans: ``window`` around the measured window,
``client`` while the next wave is built and ``serve`` while the program
serves it, besides the runtime's own events on the same threads.

Busy time is the union of a device's operation intervals inside the
window, averaged over devices; an idle gap is a stretch of the window
in which the first device runs no operation, named by the innermost
host event on the window's thread that covers its middle.
"""

from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
WINDOW_SPAN = "window"


@dataclass
class Summary:
    window_s: float                        # length of the host's window span
    busy_s: float                          # device busy, mean over devices
    modules: dict[str, list[float]]        # program name -> seconds per run
    op_s: dict[str, float]                 # operation -> total self seconds
    gaps: dict[str, float] = field(default_factory=dict)  # host event -> s

    def module_runs(self, part: str) -> list[float]:
        """Seconds of every run of the programs whose name has ``part``."""
        return [s for name, runs in self.modules.items() if part in name
                for s in runs]


def load(trace_dir: str | Path):
    from jax.profiler import ProfileData
    files = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return ProfileData.from_file(str(files[-1]))


def op_name(text: str) -> str:
    """An operation's HLO text without layouts, cut to 100 characters."""
    return re.sub(r"\{[^{}]*\}", "", text)[:100]


def _self_times(events) -> list[tuple[str, float]]:
    """(name, seconds) of each event less the events nested in it (a
    loop's body runs as operations inside the loop's own event)."""
    evs = sorted(((int(e.start_ns), int(e.start_ns + e.duration_ns),
                   e.name) for e in events), key=lambda v: (v[0], -v[1]))
    own = [b - a for a, b, _ in evs]
    stack: list[int] = []
    for i, (a, b, _) in enumerate(evs):
        while stack and evs[stack[-1]][1] <= a:
            stack.pop()
        if stack:
            own[stack[-1]] -= b - a
        stack.append(i)
    return [(name, max(o, 0) / 1e9) for (_, _, name), o in zip(evs, own)]


def _union(intervals: list[tuple[int, int]]) -> list[tuple[int, int]]:
    out: list[list[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _window(profile) -> tuple[int, int, list]:
    """The window span's bounds (ns) and the events of its thread."""
    for plane in profile.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            events = list(line.events)
            for ev in events:
                if ev.name == WINDOW_SPAN:
                    start = int(ev.start_ns)
                    return start, start + int(ev.duration_ns), events
    raise ValueError(f"no {WINDOW_SPAN!r} span in the trace")


def _host_labels(events, times: list[float]) -> list[str]:
    """The innermost host event covering each of the sorted ``times``
    (events of one thread nest, so a stack sweep finds it)."""
    spans = sorted(((int(e.start_ns), int(e.start_ns + e.duration_ns),
                     e.name) for e in events), key=lambda s: (s[0], -s[1]))
    out, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][0] <= t:
            while stack and stack[-1][1] < spans[i][0]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][1] < t:
            stack.pop()
        out.append(stack[-1][2] if stack else "(no host event)")
    return out


def summarize(profile) -> Summary:
    t0, t1, host_events = _window(profile)
    modules: dict[str, list[float]] = defaultdict(list)
    op_s: dict[str, float] = defaultdict(float)
    busy, first_busy = [], None
    for plane in profile.planes:
        if not plane.name.startswith(DEVICE_PREFIX):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        inside = [ev for ev in lines[OPS_LINE].events
                  if t0 <= ev.start_ns < t1]
        ivs = [(int(ev.start_ns), min(int(ev.start_ns + ev.duration_ns), t1))
               for ev in inside]
        for name, secs in _self_times(inside):
            op_s[op_name(name)] += secs
        for ev in (lines[MODULES_LINE].events if MODULES_LINE in lines
                   else []):
            if ev.start_ns >= t0 and ev.start_ns + ev.duration_ns <= t1:
                modules[ev.name].append(ev.duration_ns / 1e9)
        merged = _union(ivs)
        busy.append(sum(b - a for a, b in merged) / 1e9)
        if first_busy is None:
            first_busy = merged
    if not busy:
        raise ValueError("the trace holds no device operation")
    edges = [t0] + [t for iv in first_busy for t in iv] + [t1]
    idle = [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
    gaps: dict[str, float] = defaultdict(float)
    for (a, b), name in zip(idle, _host_labels(
            host_events, [(a + b) / 2 for a, b in idle])):
        gaps[name] += (b - a) / 1e9
    return Summary(window_s=(t1 - t0) / 1e9, busy_s=sum(busy) / len(busy),
                   modules=dict(modules), op_s=dict(op_s), gaps=dict(gaps))


def top(d: dict[str, float], n: int = 10) -> list[list]:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:n]]

