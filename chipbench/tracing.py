"""The device trace of a run's window, and its reduction to numbers.

:func:`capture` brackets the window with a ``jax.profiler`` session (the
Python tracer off, so the host code runs at its own speed) and loads the
result as a :class:`Trace`: the window's span on the trace clock, the
device operations and programs of each chip, and the host spans that the
harness and the runtime annotate.  The reductions below read only that
object, so the tests drive them with a small recorded trace
(:func:`Trace.from_records`).
"""
from __future__ import annotations

import contextlib
import dataclasses
import glob
import heapq
import os
import re
import shutil
import tempfile
from collections import defaultdict

#: the harness's annotation around the whole measured window
WINDOW = "chipbench/window"
#: the host spans kept: the harness's spans inside one solve, and the
#: ``bddt/<executor>/wave<n>`` span the runtime adds per wave when
#: ``profile_waves`` is on
_HOST_SPAN = re.compile(r"^(reload|spawn|barrier|drain|bddt/\w+/wave\d+)$")
_DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
_PROGRAM_ID = re.compile(r"\(\d+\)$")


@dataclasses.dataclass
class Device:
    ops: list          # [(start_ns, end_ns)] of every device operation
    programs: list     # [(program name, start_ns, end_ns)]


@dataclasses.dataclass
class Trace:
    window: tuple      # (start_ns, end_ns)
    devices: dict      # plane name -> Device
    spans: list        # [(name, start_ns, end_ns)] of host spans

    # -- the recorded form the tests read ----------------------------------
    def to_records(self) -> dict:
        return {"window": list(self.window),
                "devices": {k: {"ops": [list(o) for o in d.ops],
                                "programs": [list(p) for p in d.programs]}
                            for k, d in self.devices.items()},
                "spans": [list(s) for s in self.spans]}

    @classmethod
    def from_records(cls, rec: dict) -> "Trace":
        return cls(tuple(rec["window"]),
                   {k: Device([tuple(o) for o in d["ops"]],
                              [tuple(p) for p in d["programs"]])
                    for k, d in rec["devices"].items()},
                   [tuple(s) for s in rec["spans"]])

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9


def _program_name(name: str) -> str:
    return _PROGRAM_ID.sub("", name)


def from_xspace(path: str) -> Trace:
    """Read a profiler ``.xplane.pb``.  Device planes are ``/device:TPU:n``;
    their ``XLA Ops`` line holds the operations and their ``XLA Modules``
    line the programs.  Host spans come from every other plane."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    window, devices, spans = None, {}, []
    for plane in pd.planes:
        if _DEVICE_PLANE.match(plane.name):
            ops, progs = [], []
            for line in plane.lines:
                if line.name == "XLA Ops":
                    ops.extend((e.start_ns, e.start_ns + e.duration_ns)
                               for e in line.events)
                elif line.name == "XLA Modules":
                    progs.extend((_program_name(e.name), e.start_ns,
                                  e.start_ns + e.duration_ns)
                                 for e in line.events)
            devices[plane.name] = Device(sorted(ops), sorted(
                progs, key=lambda p: p[1]))
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name == WINDOW:
                    window = (e.start_ns, e.start_ns + e.duration_ns)
                elif _HOST_SPAN.match(e.name):
                    spans.append((e.name, e.start_ns,
                                  e.start_ns + e.duration_ns))
    if window is None:
        raise RuntimeError(f"{path}: no {WINDOW!r} span in the trace")
    spans.sort(key=lambda s: s[1])
    return Trace(window, devices, spans)


@contextlib.contextmanager
def capture(result: list):
    """Trace the enclosed block; appends the :class:`Trace` to ``result``
    once the session is stopped.  The files go to a temporary directory
    (under ``TMPDIR``) that is removed after reading."""
    import jax

    logdir = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    try:
        jax.profiler.start_trace(logdir, profiler_options=opts)
        try:
            yield
        finally:
            jax.profiler.stop_trace()
        files = glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                       "*.xplane.pb"))
        if len(files) != 1:
            raise RuntimeError(f"expected one trace file, found {files}")
        result.append(from_xspace(files[0]))
    finally:
        shutil.rmtree(logdir, ignore_errors=True)


# -- reductions ---------------------------------------------------------------
def _clip(intervals, lo, hi):
    for s, e in intervals:
        s, e = max(s, lo), min(e, hi)
        if e > s:
            yield s, e


def _union(intervals) -> list:
    """Merge sorted-or-not intervals into disjoint sorted ones."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


def busy_seconds(trace: Trace) -> float | None:
    """Seconds of the window in which some operation ran on a device,
    averaged over the devices traced; None when no device plane has an
    operation in the window."""
    lo, hi = trace.window
    per = [sum(e - s for s, e in _union(_clip(d.ops, lo, hi)))
           for d in trace.devices.values()]
    if not per or not any(per):
        return None
    return sum(per) / len(per) * 1e-9


def program_seconds(trace: Trace, match) -> float:
    """Device seconds of the programs whose name ``match`` accepts, summed
    over devices (clipped to the window)."""
    lo, hi = trace.window
    total = 0
    for d in trace.devices.values():
        for name, s, e in d.programs:
            if match(name):
                s, e = max(s, lo), min(e, hi)
                if e > s:
                    total += e - s
    return total * 1e-9


def body_matcher(body: str):
    """Accepts the program names of a task body called ``body``: the
    executors jit it (vmapped, or shard-mapped over a mesh) under the
    body's own name, which XLA reports as ``jit_<body>``."""
    pat = re.compile(r"^jit_" + re.escape(body) + r"$")
    return lambda name: bool(pat.match(name))


def top_programs(trace: Trace, top: int = 10) -> list:
    """``[[program name, device seconds]]``, the largest first, summed
    over devices and clipped to the window."""
    lo, hi = trace.window
    acc: dict = defaultdict(int)
    for d in trace.devices.values():
        for name, s, e in d.programs:
            s, e = max(s, lo), min(e, hi)
            if e > s:
                acc[name] += e - s
    best = heapq.nlargest(top, acc.items(), key=lambda kv: kv[1])
    return [[k, v * 1e-9] for k, v in best]


def _host_segments(trace: Trace, rename) -> list:
    """The window cut into segments, each named by the innermost host span
    open over it (the one opened last), or ``"other"``."""
    lo, hi = trace.window
    points = {lo, hi}
    for _, s, e in trace.spans:
        points.update(p for p in (s, e) if lo < p < hi)
    cuts = sorted(points)
    segs = []
    # spans are sorted by start; walk the cuts with the set of open spans
    starts = sorted(trace.spans, key=lambda sp: sp[1])
    nxt, open_ = 0, []
    for a, b in zip(cuts, cuts[1:]):
        while nxt < len(starts) and starts[nxt][1] <= a:
            open_.append(starts[nxt])
            nxt += 1
        open_ = [sp for sp in open_ if sp[2] > a]
        name = rename(open_[-1][0]) if open_ else "other"
        segs.append((a, b, name))
    return segs


def idle_by_host_span(trace: Trace, rename=lambda n: n, top: int = 10) \
        -> list:
    """``[[host span, idle seconds]]``: the device time of the window in
    which no operation ran, split by what the host was inside at the
    time, averaged over devices, the largest first."""
    lo, hi = trace.window
    segs = _host_segments(trace, rename)
    acc: dict = defaultdict(int)
    for d in trace.devices.values():
        busy = _union(_clip(d.ops, lo, hi))
        gaps, t = [], lo
        for s, e in busy:
            if s > t:
                gaps.append((t, s))
            t = max(t, e)
        if t < hi:
            gaps.append((t, hi))
        i = 0
        for a, b, name in segs:
            while i < len(gaps) and gaps[i][1] <= a:
                i += 1
            j = i
            while j < len(gaps) and gaps[j][0] < b:
                s, e = max(gaps[j][0], a), min(gaps[j][1], b)
                if e > s:
                    acc[name] += e - s
                j += 1
    n = max(len(trace.devices), 1)
    best = heapq.nlargest(top, acc.items(), key=lambda kv: kv[1])
    return [[k, v / n * 1e-9] for k, v in best]
