"""Reduce a profiler trace (``.xplane.pb``) to what the layer metrics read.

A TPU trace has one plane per chip (``/device:TPU:<n>``) with a line of
XLA operations (``XLA Ops``: every op, Pallas kernels among them, by name)
and a line of whole programs (``XLA Modules``: one event per executed
jitted program, named ``jit_<function>(<id>)``), and a host plane
(``/host:CPU``) whose threads carry the host spans, the benchmark's own
``TraceAnnotation``\\s among them.  Host and device events share one clock.
"""
from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"


@dataclass(frozen=True)
class Event:
    name: str
    start: int      # ns
    end: int        # ns
    where: str = ""  # device index or host thread

    @property
    def seconds(self) -> float:
        return (self.end - self.start) * 1e-9


@dataclass
class Reduced:
    ops: list[Event]       # device operations, every chip
    modules: list[Event]   # device programs, every chip
    host: list[Event]      # host spans, every thread
    n_devices: int

    def host_span(self, name: str) -> Event | None:
        """The first host span of that exact name."""
        return next((e for e in self.host if e.name == name), None)

    def within(self, events: list[Event], window: Event) -> list[Event]:
        return [e for e in events
                if e.end > window.start and e.start < window.end]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise FileNotFoundError(f"{len(paths)} xplane files under {log_dir}")
    return paths[0]


def reduce(path: str) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, modules, host, devices = [], [], [], set()
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            devices.add(m.group(1))
            for line in plane.lines:
                dest = {OPS_LINE: ops, MODULES_LINE: modules}.get(line.name)
                if dest is not None:
                    dest.extend(Event(e.name, int(e.start_ns), int(e.end_ns),
                                      m.group(1)) for e in line.events)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                host.extend(Event(e.name, int(e.start_ns), int(e.end_ns),
                                  line.name) for e in line.events)
    key = lambda e: (e.start, e.end)
    return Reduced(sorted(ops, key=key), sorted(modules, key=key),
                   sorted(host, key=key), len(devices))


def union(events: list[Event], window: Event) -> list[tuple[int, int]]:
    """Disjoint busy intervals of ``events`` clipped to ``window``."""
    spans = sorted((max(e.start, window.start), min(e.end, window.end))
                   for e in events)
    out: list[list[int]] = []
    for s, e in spans:
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_seconds(red: Reduced, window: Event) -> float:
    """Seconds in which some operation ran, averaged over the chips."""
    if not red.n_devices:
        return 0.0
    total = 0
    for dev in {e.where for e in red.ops}:
        total += sum(e - s for s, e in union(
            [o for o in red.ops if o.where == dev], window))
    return total * 1e-9 / red.n_devices


def idle_gaps(red: Reduced, window: Event) -> list[tuple[int, int]]:
    """Intervals of the window in which no operation ran on the first
    chip."""
    dev = min((e.where for e in red.ops), default=None)
    busy = union([o for o in red.ops if o.where == dev], window)
    gaps, at = [], window.start
    for s, e in busy:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    if window.end > at:
        gaps.append((at, window.end))
    return gaps


def host_doing(red: Reduced, start: int, end: int, skip: tuple[str, ...] = ()
               ) -> str:
    """The innermost host span that covers most of [start, end)."""
    best, best_key = "host (no span)", None
    for e in red.host:
        if e.start > end:
            break
        cover = min(e.end, end) - max(e.start, start)
        if cover <= 0 or e.name in skip:
            continue
        key = (cover, -(e.end - e.start))
        if best_key is None or key > best_key:
            best, best_key = e.name, key
    return best


def seconds_named(events: list[Event], pattern: str) -> tuple[float, int]:
    """(summed seconds, count) of events whose name matches ``pattern``."""
    rx = re.compile(pattern)
    hit = [e for e in events if rx.search(e.name)]
    return sum(e.seconds for e in hit), len(hit)


def top_ops(red: Reduced, window: Event, k: int = 10
            ) -> list[list]:
    """The ``k`` device operations that took most time in the window, by
    summed duration over every chip and call."""
    total: dict[str, float] = {}
    for e in red.within(red.ops, window):
        total[e.name] = total.get(e.name, 0.0) + e.seconds
    return [[n, s] for n, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:k]]
