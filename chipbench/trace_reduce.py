"""Reduce a profiler trace of the measured window to device times.

A ``--trace 1`` run wraps every unit in a ``jax.profiler.TraceAnnotation``
named :data:`UNIT_SPAN`.  The window is the span from the first unit's
start to the last unit's end on the host clock, onto which the profiler
maps the device planes, widened to any device op that the mapping puts
just outside it.  Device time is read from the ``XLA Ops`` line of
each ``/device:TPU:<n>`` plane (the ``Async XLA Ops`` line holds DMAs in
flight and is not read): busy time is the union of the op intervals, and
an op's time is its self time, its duration less that of the ops it
contains (a ``while`` holds its body's ops).  An op event's text is its
HLO instruction; a Pallas kernel shows as a ``custom-call`` named after
the jitted function that wraps it.
"""

from __future__ import annotations

import dataclasses
import re

UNIT_SPAN = "chipbench.unit"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"


@dataclasses.dataclass(frozen=True)
class Event:
    name: str              # HLO instruction name, or host span name
    start: float           # ns
    end: float             # ns
    opcode: str = ""       # HLO opcode of a device op


#: the opcode in an op event's text: the first lower-case word before "("
OPCODE = re.compile(r" ([a-z][a-z0-9-]*)\(")


def device_op(text: str, start: float, end: float) -> Event:
    """An op event from its text, ``%<name> = <shape> <opcode>(...)``."""
    name, eq, rest = text.partition(" = ")
    m = OPCODE.search(" " + rest) if eq else None
    return Event(name.lstrip("%"), start, end, m.group(1) if m else "")


def _clip(events, lo, hi):
    return [dataclasses.replace(e, start=max(e.start, lo), end=min(e.end, hi))
            for e in events if e.end > lo and e.start < hi]


def _self_times(events) -> list:
    """(event, self ns) for ``events`` of one device: an op that contains
    others, as a ``while`` contains its body's ops, keeps only the time in
    which none of them runs."""
    out, stack = [], []
    for e in sorted(events, key=lambda e: (e.start, -e.end)):
        while stack and stack[-1][0].end <= e.start:
            stack.pop()
        item = [e, e.end - e.start]
        if stack and e.end <= stack[-1][0].end:
            stack[-1][1] -= e.end - e.start
        stack.append(item)
        out.append(item)
    return [tuple(item) for item in out]


def _union(events) -> list:
    """Disjoint (start, end) intervals covering ``events``."""
    out = []
    for e in sorted(events, key=lambda e: e.start):
        if out and e.start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e.end)
        else:
            out.append([e.start, e.end])
    return out


class Reduced:
    """Device op events and host spans of one traced window."""

    def __init__(self, devices: dict, host: list):
        spans = [e for e in host if e.name == UNIT_SPAN]
        if not spans:
            raise ValueError(f"the trace holds no {UNIT_SPAN!r} span")
        if not devices:
            raise ValueError("the trace holds no TPU device plane")
        self.units = len(spans)
        # Only the units run on the device while the profiler is on, so
        # every op belongs to the window.  The device clock, mapped onto
        # the host's, can put an op's start up to a millisecond before the
        # host span that dispatched it: the window takes in every op.
        ops = [e for v in devices.values() for e in v]
        self.lo = min(e.start for e in spans + ops)
        self.hi = max(e.end for e in spans + ops)
        self.devices = {k: _clip(v, self.lo, self.hi)
                        for k, v in sorted(devices.items())}
        self.self_times = {k: _self_times(v)
                           for k, v in self.devices.items()}
        self.host = [e for e in _clip(host, self.lo, self.hi)
                     if e.end > e.start]

    @property
    def window_s(self) -> float:
        return (self.hi - self.lo) * 1e-9

    def seconds(self, select) -> list:
        """Per device, self seconds of the op events ``select`` accepts."""
        return [sum(t for e, t in ops if select(e)) * 1e-9
                for ops in self.self_times.values()]

    def busy(self) -> list:
        """Per device, seconds in which some op ran."""
        return [sum(b - a for a, b in _union(ops)) * 1e-9
                for ops in self.devices.values()]

    def top_ops(self, k: int = 10) -> list:
        """The ``k`` ops with the most self time, instances of one
        instruction (``fusion.3``, ``fusion.7``) summed under its base
        name, in mean seconds per device."""
        total = {}
        for ops in self.self_times.values():
            for e, t in ops:
                base = re.sub(r"\.\d+$", "", e.name)
                total[base] = total.get(base, 0.0) + t
        n = len(self.devices)
        ranked = sorted(total.items(), key=lambda kv: -kv[1])[:k]
        return [[name, ns * 1e-9 / n] for name, ns in ranked]

    def idle_gaps(self, k: int = 10) -> list:
        """The ``k`` longest gaps in device busy time, on any device, each
        labelled with the shortest host span that covers its midpoint."""
        gaps = []
        for ops in self.devices.values():
            edges = [self.lo] + [t for iv in _union(ops) for t in iv] \
                + [self.hi]
            gaps += [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for a, b in gaps[:k]:
            mid = (a + b) / 2
            cover = [e for e in self.host if e.start <= mid <= e.end]
            label = (min(cover, key=lambda e: e.end - e.start).name
                     if cover else "no host span")
            out.append([label, (b - a) * 1e-9])
        return out


def from_profile(profile) -> Reduced:
    """A :class:`Reduced` from a ``jax.profiler.ProfileData``."""
    devices, host = {}, []
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            devices[plane.name] = [
                device_op(e.name, e.start_ns, e.start_ns + e.duration_ns)
                for line in plane.lines if line.name == OPS_LINE
                for e in line.events]
        elif plane.name == HOST_PLANE:
            host += [Event(e.name, e.start_ns, e.start_ns + e.duration_ns)
                     for line in plane.lines for e in line.events]
    return Reduced(devices, host)


def load(path: str) -> Reduced:
    """Read an ``.xplane.pb`` file, or a gzipped one."""
    import gzip
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            return from_profile(ProfileData.from_serialized_xspace(f.read()))
    return from_profile(ProfileData.from_file(path))
