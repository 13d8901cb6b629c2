"""From a profiler trace to the numbers the per-layer metrics read.

A traced run writes an ``.xplane.pb`` (``jax.profiler``); :func:`load`
reads it with ``jax.profiler.ProfileData`` into plain event tuples, and
:func:`reduce_trace` works on those alone, so the arithmetic is tested on
hand-made events and on a small recorded trace without a chip:

* the window: the host span named ``window`` that the harness wraps around
  the measured loop;
* device busy time: the union of the op intervals on each device's
  ``XLA Ops`` line, clipped to the window, averaged over the devices that
  ran anything;
* op time by name: each op's self time (its duration less the ops nested
  inside it on the same line), so a loop op that contains its body is not
  counted twice;
* idle gaps: the stretches of the window in which no op ran, each labelled
  by what the host was doing: the shortest host event (any thread) that
  covers at least half of the gap. Gaps under 0.1 ms lie between the ops
  of one running program and are summed under one label.
"""
from __future__ import annotations

import collections
import glob
import os
import re
from typing import Dict, Iterable, List, NamedTuple, Optional, Sequence, \
    Tuple

DEVICE_PREFIX = "/device:"
HOST_PREFIX = "/host:"
OPS_LINE = "XLA Ops"
WINDOW_SPAN = "window"
NO_HOST_SPAN = "(no host span)"
# gaps shorter than this lie between the ops of one running program; they
# are summed under one label instead of being attributed to the host
SHORT_GAP_NS = 100e3
SHORT_GAP = "(gaps under 0.1 ms, inside programs)"


class Event(NamedTuple):
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


class Trace(NamedTuple):
    """Device op events per device plane, and host events (all threads)."""
    devices: Dict[str, List[Event]]
    host: List[Event]


def find_xplane(log_dir: str) -> str:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` log directory."""
    files = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(files, key=os.path.getmtime)


_HLO = re.compile(r"^%?(\S+) = (.*?) ([a-z][a-z0-9_-]*)\(")
_LAYOUT = re.compile(r"\{[^{}]*\}")


def op_name(hlo: str) -> str:
    """A device op's name from its event name, which on a TPU is the whole
    HLO instruction: ``"%sort.48 = (s32[8]{0}, ...) sort(...), ..."``
    becomes ``"sort.48 sort (s32[8], ...)"`` (instruction name, opcode,
    result shape without layouts). Other names pass through."""
    m = _HLO.match(hlo)
    if not m:
        return hlo
    name, shape, opcode = m.groups()
    while _LAYOUT.search(shape):
        shape = _LAYOUT.sub("", shape)
    return f"{name} {opcode} {shape}"


def load(path: str) -> Trace:
    """Read an ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    devices: Dict[str, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            lines = [line for line in plane.lines if line.name == OPS_LINE]
            if lines:
                devices[plane.name] = [
                    Event(op_name(e.name), e.start_ns, e.duration_ns)
                    for line in lines for e in line.events]
        elif plane.name.startswith(HOST_PREFIX):
            host.extend(Event(e.name, e.start_ns, e.duration_ns)
                        for line in plane.lines for e in line.events)
    return Trace(devices, host)


def find_window(host: Iterable[Event], name: str = WINDOW_SPAN
                ) -> Tuple[float, float]:
    """(start_ns, end_ns) of the host span ``name``; the longest if the
    trace holds several."""
    spans = [e for e in host if e.name == name]
    if not spans:
        raise ValueError(f"the trace holds no host span named {name!r}")
    w = max(spans, key=lambda e: e.dur_ns)
    return w.start_ns, w.end_ns


def union(events: Iterable[Event], lo: float, hi: float
          ) -> List[Tuple[float, float]]:
    """The union of the events' intervals clipped to ``[lo, hi]``, as
    sorted disjoint ``(start, end)`` pairs."""
    spans = sorted((max(e.start_ns, lo), min(e.end_ns, hi))
                   for e in events)
    out: List[Tuple[float, float]] = []
    for s, t in spans:
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1] = (out[-1][0], max(out[-1][1], t))
        else:
            out.append((s, t))
    return out


def gaps(busy: Sequence[Tuple[float, float]], lo: float, hi: float
         ) -> List[Tuple[float, float]]:
    """The stretches of ``[lo, hi]`` that ``busy`` (disjoint, sorted)
    leaves uncovered."""
    out, t = [], lo
    for s, e in busy:
        if s > t:
            out.append((t, s))
        t = max(t, e)
    if hi > t:
        out.append((t, hi))
    return out


def self_times(events: Sequence[Event], lo: float, hi: float
               ) -> Tuple[Dict[str, float], Dict[str, int]]:
    """Self time (ns) and count of each op name among the events that
    start inside ``[lo, hi)``. An event nested in another on the same line
    (a loop op and its body) is subtracted from its parent."""
    inside = sorted((e for e in events if lo <= e.start_ns < hi),
                    key=lambda e: (e.start_ns, -e.dur_ns))
    self_ns: Dict[str, float] = collections.defaultdict(float)
    count: Dict[str, int] = collections.defaultdict(int)
    stack: List[Event] = []
    for e in inside:
        while stack and stack[-1].end_ns <= e.start_ns:
            stack.pop()
        self_ns[e.name] += e.dur_ns
        count[e.name] += 1
        if stack:
            self_ns[stack[-1].name] -= min(e.dur_ns,
                                           stack[-1].end_ns - e.start_ns)
        stack.append(e)
    return dict(self_ns), dict(count)


class HostIndex:
    """Host events as arrays, for labelling many gaps quickly."""

    def __init__(self, host: Sequence[Event]):
        import numpy as np
        self.names = [e.name for e in host]
        self.start = np.asarray([e.start_ns for e in host], np.float64)
        self.end = self.start + np.asarray([e.dur_ns for e in host],
                                           np.float64)

    def label(self, gap: Tuple[float, float]) -> str:
        """What the host was doing during ``gap``: the shortest host event
        that covers at least half of it."""
        import numpy as np
        s, t = gap
        cover = np.minimum(self.end, t) - np.maximum(self.start, s)
        ok = np.flatnonzero(cover >= 0.5 * (t - s))
        if not ok.size:
            return NO_HOST_SPAN
        dur = self.end[ok] - self.start[ok]
        return self.names[int(ok[np.argmin(dur)])]


class Reduction(NamedTuple):
    window_s: float
    busy_s: float                   # mean over the devices that ran ops
    op_self_s: Dict[str, float]     # summed over devices
    op_count: Dict[str, int]
    idle_by_label: Dict[str, float]  # seconds, first device
    longest_gaps: List[Tuple[str, float]]


def reduce_trace(trace: Trace, window: Optional[Tuple[float, float]] = None
                 ) -> Reduction:
    """Reduce a :class:`Trace` over ``window`` (default: the ``window``
    host span)."""
    lo, hi = window if window is not None else find_window(trace.host)
    used = {name: ops for name, ops in sorted(trace.devices.items())
            if any(lo <= e.start_ns < hi for e in ops)}
    if not used:
        raise ValueError("no device op ran inside the traced window")
    busy_ns, op_self, op_count = [], collections.Counter(), \
        collections.Counter()
    idle: Dict[str, float] = collections.defaultdict(float)
    longest: List[Tuple[str, float]] = []
    index = HostIndex(trace.host)
    for i, ops in enumerate(used.values()):
        busy = union(ops, lo, hi)
        busy_ns.append(sum(t - s for s, t in busy))
        st, ct = self_times(ops, lo, hi)
        op_self.update(st)
        op_count.update(ct)
        if i == 0:
            for g in gaps(busy, lo, hi):
                if g[1] - g[0] < SHORT_GAP_NS:
                    idle[SHORT_GAP] += (g[1] - g[0]) * 1e-9
                    continue
                label = index.label(g)
                idle[label] += (g[1] - g[0]) * 1e-9
                longest.append((label, (g[1] - g[0]) * 1e-9))
    longest.sort(key=lambda x: -x[1])
    return Reduction(
        window_s=(hi - lo) * 1e-9,
        busy_s=sum(busy_ns) / len(busy_ns) * 1e-9,
        op_self_s={k: v * 1e-9 for k, v in op_self.items()},
        op_count=dict(op_count),
        idle_by_label=dict(idle),
        longest_gaps=longest)


def top(mapping: Dict[str, float], n: int = 10) -> List[List]:
    """The ``n`` largest entries as ``[[name, value], ...]``."""
    return [[k, v] for k, v in sorted(mapping.items(),
                                      key=lambda kv: -kv[1])[:n]]


def breakdown(red: Reduction, n: int = 10) -> dict:
    """The result line's ``breakdown``: device ops by self time, and idle
    time by what the host was doing."""
    return {"device_ops": top(red.op_self_s, n),
            "idle_gaps": top(red.idle_by_label, n)}


def idle_percent(red: Optional[Reduction]) -> Optional[float]:
    """The device's idle share of the traced window, in %: one minus the
    union of its op intervals over the window."""
    if red is None or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)


def kernel_time(red: Reduction, prefix: str) -> Tuple[float, int]:
    """(summed self seconds, event count) of the ops whose name starts
    with ``prefix`` (a kernel's HLO instructions carry its jit name)."""
    names = [k for k in red.op_self_s if k.startswith(prefix)]
    return (sum(red.op_self_s[k] for k in names),
            sum(red.op_count[k] for k in names))
