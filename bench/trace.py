"""The one reduction from a profiler trace (``.xplane.pb``) to the
benchmark's device numbers.

* the window: the host span ``bench.window`` that the harness opens
  around the measured fits, on the profiler's own clock;
* busy: the union of the intervals in which an operation ran on a
  device (the ``XLA Ops`` line of each ``/device:TPU:<i>`` plane),
  clipped to the window and averaged over the chips;
* idle gaps: the holes in that union inside the window, each named by
  the innermost host event that covers its middle (what the host was
  doing while the device waited);
* per-kernel device time: the summed durations of the device events
  whose operation name contains the kernel's trace name;
* the ``breakdown``: the ten device operations that took most time and
  the ten host activities that left the device idle longest.
"""
from __future__ import annotations

import dataclasses
import glob
import gzip
import heapq
import os
import re
from collections import defaultdict

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
TOP = 10


@dataclasses.dataclass
class Trace:
    path: str
    window: tuple                 # (start_ns, end_ns) of the window span
    device_ops: list              # per chip: (starts, ends, names) arrays
    host: tuple                   # (starts, ends, names) arrays

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def _union(self, chip: int) -> np.ndarray:
        """Merged busy intervals of one chip, clipped to the window,
        as an (m, 2) array of ns."""
        starts, ends, _ = self.device_ops[chip]
        w0, w1 = self.window
        s = np.clip(starts, w0, w1)
        e = np.clip(ends, w0, w1)
        keep = e > s
        s, e = s[keep], e[keep]
        if not len(s):
            return np.zeros((0, 2), np.int64)
        order = np.argsort(s, kind="stable")
        s, e = s[order], np.maximum.accumulate(e[order])
        # a new interval starts where it begins after everything before
        new = np.ones(len(s), bool)
        new[1:] = s[1:] > e[:-1]
        idx = np.flatnonzero(new)
        return np.stack([s[idx], np.append(e[idx[1:] - 1], e[-1])], axis=1)

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.device_ops:
            return 0.0
        per_chip = [float(np.sum(u[:, 1] - u[:, 0])) / 1e9
                    for u in (self._union(c)
                              for c in range(len(self.device_ops)))]
        return float(np.mean(per_chip))

    def kernel(self, trace_name: str) -> tuple:
        """(events, device seconds) of one kernel inside the window,
        summed over the chips."""
        count, total = 0, 0
        w0, w1 = self.window
        for starts, ends, names in self.device_ops:
            hit = np.array([trace_name in n for n in names], bool)
            hit &= (starts >= w0) & (ends <= w1)
            count += int(hit.sum())
            total += int(np.sum(ends[hit] - starts[hit]))
        return count, total / 1e9

    def idle_gaps(self, chip: int = 0) -> list:
        """[(start_ns, end_ns, host activity)] for each hole in the busy
        union of ``chip`` inside the window."""
        u = self._union(chip)
        w0, w1 = self.window
        edges = np.concatenate([[w0], u.ravel(), [w1]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        names = self._covering((gaps[:, 0] + gaps[:, 1]) // 2)
        return [(int(g0), int(g1), name)
                for (g0, g1), name in zip(gaps, names)]

    def _covering(self, points) -> list:
        """The name of the shortest host event that covers each of the
        ascending ``points`` (the earlier event on a tie): a sweep that
        adds events as they start and drops them once they have ended."""
        hs, he, hn = self.host
        order = np.argsort(hs, kind="stable").tolist()
        starts, ends = hs.tolist(), he.tolist()
        heap, j, out = [], 0, []
        for p in np.asarray(points).tolist():
            while j < len(order) and starts[order[j]] <= p:
                i = order[j]
                heapq.heappush(heap, (ends[i] - starts[i], i))
                j += 1
            while heap and ends[heap[0][1]] < p:
                heapq.heappop(heap)
            out.append(hn[heap[0][1]] if heap else "(no host event)")
        return out

    def breakdown(self) -> dict:
        """Top device operations and idle gaps, seconds as measured."""
        ops = defaultdict(int)
        w0, w1 = self.window
        for starts, ends, names in self.device_ops:
            for s, e, n in zip(starts, ends, names):
                if s >= w0 and e <= w1:
                    ops[n] += int(e - s)
        n_chips = max(1, len(self.device_ops))
        gaps = defaultdict(int)
        for c in range(len(self.device_ops)):
            for g0, g1, name in self.idle_gaps(c):
                gaps[name] += g1 - g0

        def top(d):
            return [[k, v / 1e9 / n_chips]
                    for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(ops), "idle_gaps": top(gaps)}


def op_name(event_name: str) -> str:
    """The operation's own name: a device event is named by its HLO
    instruction (``%name = type op(operands...)``), whose operands may
    name other operations."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def _events(line, name=lambda n: n):
    evs = list(line.events)
    starts = np.array([e.start_ns for e in evs], np.int64)
    durs = np.array([e.duration_ns for e in evs], np.int64)
    names = [name(e.name) for e in evs]
    return starts, starts + durs, names


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def load(path: str, window_span: str) -> Trace:
    """Read a trace file, gzipped or not (or the newest one under a
    directory)."""
    import jax
    if os.path.isdir(path):
        path = find_xplane(path)
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as fh:
            data = jax.profiler.ProfileData.from_serialized_xspace(fh.read())
    else:
        data = jax.profiler.ProfileData.from_file(path)
    return from_profile(data, window_span, path)


def from_profile(data, window_span: str, path: str = "") -> Trace:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`Trace`."""
    devices = {}
    hs, he, hn = [], [], []
    for plane in data.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices[int(m.group(1))] = _events(line, op_name)
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                s, e, n = _events(line)
                hs.append(s)
                he.append(e)
                hn.extend(n)
    hs = np.concatenate(hs) if hs else np.zeros(0, np.int64)
    he = np.concatenate(he) if he else np.zeros(0, np.int64)
    hn = np.array(hn, dtype=object)
    marks = np.flatnonzero(hn == window_span)
    if not len(marks):
        raise ValueError(f"no {window_span!r} span in {path}")
    i = marks[np.argmax(he[marks] - hs[marks])]
    return Trace(path=path, window=(int(hs[i]), int(he[i])),
                 device_ops=[devices[k] for k in sorted(devices)],
                 host=(hs, he, hn))
