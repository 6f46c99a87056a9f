"""Capture of the profiler's trace over the measured window, and its
reduction to device busy time, kernel times and labelled idle gaps.

The reduction works on ``Trace``, a plain record of device operations and
host spans on one clock; ``load`` fills it from the ``.xplane.pb`` file
that ``jax.profiler`` writes, and the tests fill it by hand."""
from __future__ import annotations

import glob
import os
import re
import shutil
import tempfile
from dataclasses import dataclass, field

import jax

# host spans the benchmark writes around its calls into the program
SPAN_NAMES = ("plan", "shard", "place", "traverse", "finalize")
WINDOW_SPAN = "window"  # the span around the whole measured window
_DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):(\d+)$")
_OPS_LINE = "XLA Ops"


@dataclass
class Trace:
    """Device operations and host spans in nanoseconds on one clock.

    ``devices`` maps a device id to its operations ``(name, start, dur)``;
    ``spans`` holds the benchmark's host spans ``(name, start, dur)``;
    ``window`` is the traced interval ``(start, end)``."""

    window: tuple[int, int]
    devices: dict[int, list[tuple[str, int, int]]] = field(
        default_factory=dict)
    spans: list[tuple[str, int, int]] = field(default_factory=list)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _union(intervals, lo: int, hi: int) -> list[tuple[int, int]]:
    """Merged ``[start, end)`` intervals, clipped to ``[lo, hi)``."""
    out: list[list[int]] = []
    for s, e in sorted((max(s, lo), min(s + d, hi)) for _, s, d in intervals):
        if e <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(tr: Trace) -> float:
    """Seconds in which some operation ran on the device, averaged over
    the devices in the trace."""
    if not tr.devices:
        return 0.0
    lo, hi = tr.window
    tot = sum(sum(e - s for s, e in _union(ops, lo, hi))
              for ops in tr.devices.values())
    return tot * 1e-9 / len(tr.devices)


def idle_share(tr: Trace) -> float | None:
    """1 − busy ÷ window; None where the trace saw no device."""
    if not tr.devices or tr.window_s <= 0:
        return None
    return 1.0 - busy_s(tr) / tr.window_s


def op_seconds(tr: Trace, pattern: str) -> float:
    """Summed device seconds of the operations whose name matches
    ``pattern`` (a regular expression), over every device."""
    rx = re.compile(pattern)
    return sum(d for ops in tr.devices.values() for n, _, d in ops
               if rx.search(n)) * 1e-9


def op_events(tr: Trace, pattern: str) -> list[tuple[str, int, int]]:
    rx = re.compile(pattern)
    return [ev for ops in tr.devices.values() for ev in ops
            if rx.search(ev[0])]


def short_name(op: str) -> str:
    """``%fusion.3 = f32[8]{0:T(1024)} fusion(...)`` → ``fusion.3
    f32[8]``: the trace names a device operation by its whole HLO
    instruction; keep its name and the type of its result."""
    name, _, rest = op.partition(" = ")
    out = re.match(r"\(?([a-z0-9]+\[[0-9,]*\])", rest)
    return name.lstrip("%") + (" " + out.group(1) if out else "")


def self_times(ops) -> dict[str, int]:
    """Device time of each operation name less the time of the operations
    nested inside it (a loop's body runs inside the loop's own event)."""
    tot: dict[str, int] = {}
    stack: list[list] = []   # [name, end, nested time]
    for n, s, d in sorted(ops, key=lambda o: (o[1], -o[2])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            tot[top[0]] = tot.get(top[0], 0) - top[2]
        if stack:
            stack[-1][2] += d
        tot[n] = tot.get(n, 0) + d
        stack.append([n, s + d, 0])
    for top in stack:
        tot[top[0]] = tot.get(top[0], 0) - top[2]
    return tot


def top_ops(tr: Trace, k: int = 10) -> list[list]:
    """The ``k`` operations that took most device time of their own
    (nested operations' time taken out), in seconds summed over
    devices."""
    tot: dict[str, int] = {}
    for ops in tr.devices.values():
        for n, d in self_times(ops).items():
            n = short_name(n)
            tot[n] = tot.get(n, 0) + d
    best = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
    return [[n, d * 1e-9] for n, d in best]


def idle_gaps(tr: Trace, k: int = 10) -> list[list]:
    """The ``k`` longest intervals in which the first device ran nothing,
    each labelled with the host span that covers most of it ("none" where
    no span does), in seconds."""
    if not tr.devices:
        return []
    lo, hi = tr.window
    busy = _union(tr.devices[min(tr.devices)], lo, hi)
    gaps, at = [], lo
    for s, e in busy + [(hi, hi)]:
        if s > at:
            gaps.append((at, s))
        at = max(at, e)
    gaps.sort(key=lambda g: g[0] - g[1])
    out = []
    for s, e in gaps[:k]:
        cover: dict[str, int] = {}
        for n, ss, d in tr.spans:
            ov = min(e, ss + d) - max(s, ss)
            if ov > 0:
                cover[n] = cover.get(n, 0) + ov
        label = max(cover, key=cover.get) if cover else "none"
        out.append([label, (e - s) * 1e-9])
    return out


class Capture:
    """``with Capture() as cap:`` traces the block with the JAX profiler
    into a temporary directory; ``cap.trace`` is the reduced ``Trace``
    and the directory is removed on exit."""

    def __enter__(self):
        self.dir = tempfile.mkdtemp(prefix="bench-trace-")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0   # host spans are ours, not per call
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        return self

    def __exit__(self, *exc):
        try:
            jax.profiler.stop_trace()
            if exc[0] is None:
                files = glob.glob(os.path.join(
                    self.dir, "plugins", "profile", "*", "*.xplane.pb"))
                if len(files) != 1:
                    raise RuntimeError(f"expected one trace file, found "
                                       f"{files}")
                self.trace = load(files[0])
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return False


def load(path: str) -> Trace:
    """Read a profiler ``.xplane.pb``: the device planes' operations and
    the host's benchmark spans. The window is the ``WINDOW_SPAN`` span."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window = (0, 0)
    devices: dict[int, list] = {}
    spans: list = []
    for plane in pd.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    devices[int(m.group(2))] = [
                        (e.name, int(e.start_ns), int(e.duration_ns))
                        for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in SPAN_NAMES:
                        spans.append((e.name, int(e.start_ns),
                                      int(e.duration_ns)))
                    elif e.name == WINDOW_SPAN:
                        window = (int(e.start_ns),
                                  int(e.start_ns + e.duration_ns))
    return Trace(window, devices, spans)
