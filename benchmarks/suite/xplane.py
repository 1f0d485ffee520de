"""From a profiler trace to intervals, and from intervals to numbers.

The arithmetic (busy union, idle gaps, self time) works on
plain ``(start, end)`` lists, so the tests check it on hand-made
intervals. Reading the ``.xplane.pb`` needs nothing but
``jax.profiler.ProfileData``.

A device plane is one chip (``/device:TPU:<n>``); its ``XLA Ops`` line
holds one event per executed HLO op, parents (``while``, ``call``) around
their children; an asynchronous op (a collective, a copy) is there as a
short ``-start`` and a ``-done`` that lasts as long as the core waits for
it, and its whole flight, start to done, is on the ``Async XLA Ops``
line. Host spans are the ``TraceAnnotation`` events the harness
opens itself; they carry the ``SPAN_PREFIX`` so that nothing else on the
host's timeline is mistaken for them.
"""

import dataclasses
import glob
import os
import re

SPAN_PREFIX = "bench:"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "xla ops"
ASYNC_LINE = "async xla ops"


def short_name(text):
    """An op event is named by its whole HLO instruction. Cut it to
    ``<instruction> <opcode>[:<custom call target>]``, e.g.
    ``fusion.12 fusion``, ``copy.456.remat copy``,
    ``attn.135 custom-call:tpu_custom_call`` (a Pallas kernel)."""
    head, sep, rest = text.partition(" = ")
    if not sep:
        return text
    op = re.search(r"[\])}] ([a-z][\w\-]*)\(", rest)
    target = re.search(r'custom_call_target="([^"]+)"', rest)
    return (f"{head.lstrip('%')} {op.group(1) if op else '?'}" +
            (f":{target.group(1)}" if target else ""))


def category(name):
    """``short_name`` without instance numbers: ``copy.456.remat copy``
    -> ``copy copy``."""
    instr, _, op = name.partition(" ")
    return (re.sub(r"(\.\d+|\.remat\d*|\.clone)+$", "", instr) + " " + op).strip()


def merge(intervals):
    """Union of ``(start, end)`` intervals as a sorted disjoint list."""
    out = []
    for s, e in sorted(i for i in intervals if i[1] > i[0]):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1] = (out[-1][0], e)
        else:
            out.append((s, e))
    return out


def union_length(intervals):
    return sum(e - s for s, e in merge(intervals))


def clip(intervals, window):
    w0, w1 = window
    return [(max(s, w0), min(e, w1)) for s, e in intervals
            if min(e, w1) > max(s, w0)]


def gaps(intervals, window):
    """The parts of ``window`` that no interval covers, in order."""
    w0, w1 = window
    out, at = [], w0
    for s, e in merge(clip(intervals, window)):
        if s > at:
            out.append((at, s))
        at = max(at, e)
    if w1 > at:
        out.append((at, w1))
    return out


def self_times(events):
    """``[(name, self_seconds)]`` per event of one timeline whose events
    nest: an event's self time is its duration less what its direct
    children cover, so a ``while`` around a layer loop does not count the
    loop's ops twice. ``events``: ``(name, start, end)``."""
    out, stack = [], []     # stack of [name, start, end, child_time]

    def close():
        name, s, e, child = stack.pop()
        out.append((name, max(0.0, (e - s) - child)))
        if stack:
            stack[-1][3] += e - s

    for name, s, e in sorted(events, key=lambda ev: (ev[1], -ev[2])):
        while stack and s >= stack[-1][2]:
            close()
        stack.append([name, s, min(e, stack[-1][2]) if stack else e, 0.0])
    while stack:
        close()
    return out


def innermost_span(spans, t):
    """Name of the latest-started host span that covers instant ``t``."""
    best = None
    for name, s, e in spans:
        if s <= t < e and (best is None or s >= best[1]):
            best = (name, s)
    return best[0] if best else "no_span"


@dataclasses.dataclass
class Trace:
    """Seconds on the profiler's clock. ``devices``: chip index ->
    ``[(op name, start, end)]``; ``spans``: ``[(name, start, end)]`` with
    the prefix cut off."""
    devices: dict
    spans: list
    asyncs: dict = dataclasses.field(default_factory=dict)

    def window(self):
        """First op start to last op end over all chips. The traced
        segment starts and ends with the device at work (steady state),
        so this is the traced window on the device's own clock."""
        starts = [ev[1] for evs in self.devices.values() for ev in evs]
        ends = [ev[2] for evs in self.devices.values() for ev in evs]
        return (min(starts), max(ends))

    def busy_seconds(self):
        """Union of op intervals, averaged over the chips used."""
        win = self.window()
        per_chip = [union_length(clip([(s, e) for _, s, e in evs], win))
                    for evs in self.devices.values()]
        return sum(per_chip) / len(per_chip)

    def op_seconds(self, pattern, device=None):
        """Self time of the ops whose name matches ``pattern``, averaged
        over chips (or on one)."""
        rx = re.compile(pattern)
        chips = [device] if device is not None else sorted(self.devices)
        total = 0.0
        for d in chips:
            total += sum(t for n, t in self_times(self.devices[d])
                         if rx.search(n))
        return total / len(chips)

    def flight_seconds(self, pattern):
        """Time during which an op matching ``pattern`` is under way,
        hidden or not: the union of the matching asynchronous flights
        and of the matching ops on the core's own line (a synchronous
        collective), averaged over chips."""
        rx = re.compile(pattern)
        per_chip = [union_length(
            [(s, e) for n, s, e in self.asyncs.get(d, []) + self.devices[d]
             if rx.search(n)]) for d in sorted(self.devices)]
        return sum(per_chip) / len(per_chip)

    def top_ops(self, n=10):
        """``[[name, seconds]]``: self time by op name on the first chip,
        instance numbers merged (``category``)."""
        acc = {}
        for name, t in self_times(self.devices[min(self.devices)]):
            key = category(name)
            acc[key] = acc.get(key, 0.0) + t
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]

    def top_gaps(self, n=10):
        """``[[host span, seconds]]``: idle time of the first chip by the
        harness span open on the host at the middle of each gap."""
        evs = self.devices[min(self.devices)]
        acc = {}
        for g0, g1 in gaps([(s, e) for _, s, e in evs], self.window()):
            name = innermost_span(self.spans, 0.5 * (g0 + g1))
            acc[name] = acc.get(name, 0.0) + (g1 - g0)
        return [[k, v] for k, v in
                sorted(acc.items(), key=lambda kv: -kv[1])[:n]]


def load(trace_dir):
    """Parse every ``.xplane.pb`` under ``trace_dir``. Returns None where
    no device plane holds an op (a CPU trace): readers then have nothing
    to read."""
    from jax.profiler import ProfileData

    devices, asyncs, spans = {}, {}, []
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    for path in sorted(paths):
        for plane in ProfileData.from_file(path).planes:
            m = DEVICE_PLANE.match(plane.name)
            if m:
                for line in plane.lines:
                    into = {OPS_LINE: devices, ASYNC_LINE: asyncs}.get(
                        line.name.lower())
                    if into is None:
                        continue
                    into.setdefault(int(m.group(1)), []).extend(
                        (short_name(ev.name), ev.start_ns * 1e-9,
                         (ev.start_ns + ev.duration_ns) * 1e-9)
                        for ev in line.events)
            elif plane.name.startswith("/host:"):
                for line in plane.lines:
                    for ev in line.events:
                        if ev.name.startswith(SPAN_PREFIX):
                            spans.append(
                                (ev.name[len(SPAN_PREFIX):],
                                 ev.start_ns * 1e-9,
                                 (ev.start_ns + ev.duration_ns) * 1e-9))
    devices = {d: evs for d, evs in devices.items() if evs}
    if not devices:
        return None
    return Trace(devices=devices, spans=spans, asyncs=asyncs)


def describe(trace_dir):
    """Planes, lines and event counts of a trace, for looking at one by
    hand before trusting a reader."""
    from jax.profiler import ProfileData

    out = []
    for path in sorted(glob.glob(os.path.join(
            trace_dir, "**", "*.xplane.pb"), recursive=True)):
        for plane in ProfileData.from_file(path).planes:
            for line in plane.lines:
                evs = list(line.events)
                out.append({
                    "plane": plane.name, "line": line.name,
                    "events": len(evs),
                    "first": [[e.name[:60], e.start_ns, e.duration_ns]
                              for e in evs[:3]]})
    return out
