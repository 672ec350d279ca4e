"""The program's own spans in a profiler trace: interval arithmetic over
the ``repro.*`` spans (DESIGN.md §13.1) that ``bench/trace.py`` keeps on
the host line, on the same clock as the device's events.

Every set of intervals here is an (m, 2) array of ns, sorted, disjoint
and clipped to the window.
"""
from __future__ import annotations

import numpy as np


def merge(starts, ends, window) -> np.ndarray:
    """The union of the intervals [starts, ends) clipped to ``window``."""
    w0, w1 = window
    s = np.clip(np.asarray(starts, np.int64), w0, w1)
    e = np.clip(np.asarray(ends, np.int64), w0, w1)
    keep = e > s
    s, e = s[keep], e[keep]
    if not len(s):
        return np.zeros((0, 2), np.int64)
    order = np.argsort(s, kind="stable")
    s, e = s[order], np.maximum.accumulate(e[order])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    idx = np.flatnonzero(new)
    return np.stack([s[idx], np.append(e[idx[1:] - 1], e[-1])], axis=1)


def spans(trace, name: str) -> np.ndarray:
    """The time covered by the host spans named exactly ``name``."""
    hs, he, hn = trace.host
    hit = hn == name
    return merge(hs[hit], he[hit], trace.window)


def idle(trace, chip: int) -> np.ndarray:
    """The holes in one chip's busy union inside the window."""
    w0, w1 = trace.window
    edges = np.concatenate([[w0], trace._union(chip).ravel(), [w1]])
    edges = edges.reshape(-1, 2)
    return edges[edges[:, 1] > edges[:, 0]]


def total_ns(a: np.ndarray) -> int:
    return int(np.sum(a[:, 1] - a[:, 0]))


def overlap_ns(a: np.ndarray, b: np.ndarray) -> int:
    """The ns that two interval sets share."""
    i = j = total = 0
    while i < len(a) and j < len(b):
        lo = max(a[i, 0], b[j, 0])
        hi = min(a[i, 1], b[j, 1])
        if hi > lo:
            total += int(hi - lo)
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return total
