"""Reduce a JAX profiler trace to the numbers the benchmark reports.

A trace is read into plain data (``read``): planes, each with its lines of
``(name, start_ns, duration_ns)`` events.  ``summarize`` then works on that
data alone:

* the window is the harness's ``window`` span on the host;
* a device is a plane named ``/device:<KIND>:<n>``; its busy time is the
  union of its ``XLA Ops`` intervals inside the window (its ``XLA Modules``
  intervals where it records no op line), averaged over the devices;
* launches are the ``XLA Modules`` events (one per program execution)
  that start in the window, averaged over the devices;
* the top device ops are summed by name over the window, each name
  prefixed with the program (``XLA Modules`` event) it ran in;
* each idle gap (window time that no op covers on a device) is labelled by
  the innermost harness span open at its midpoint: ``sql``,
  ``execute:<template>``, ``fetch``, or ``window`` between them.
"""

from __future__ import annotations

import glob
import os
import re
from dataclasses import dataclass, field

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:[A-Z]+:\d+$")
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
WINDOW_SPAN = "window"
TOP = 10
NAME_CHARS = 120


def is_harness_span(name: str) -> bool:
    return name in (WINDOW_SPAN, "sql", "fetch") \
        or name.startswith("execute:")


@dataclass
class Summary:
    window_s: float
    busy_s: float                 # per device, averaged
    launches: float               # per device, averaged
    devices: int
    device_ops: list = field(default_factory=list)   # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)    # [[label, seconds]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one trace under {log_dir}, "
                           f"found {len(found)}")
    return found[0]


def read(path: str) -> list:
    """``[(plane_name, {line_name: [(name, start_ns, dur_ns), ...]})]``
    for the device planes and the harness's host spans; other host events
    are dropped while reading."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        lines = {}
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (MODULE_LINE, OP_LINE):
                continue
            evs = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                   if device or is_harness_span(e.name)]
            if evs:
                lines.setdefault(line.name, []).extend(evs)
        if lines:
            planes.append((plane.name, lines))
    return planes


def _union(starts: np.ndarray, ends: np.ndarray):
    """Merged intervals of ``[starts, ends)``, as two sorted arrays."""
    if starts.size == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.r_[True, s[1:] > e[:-1]]
    last = np.r_[new[1:], True]
    return s[new], e[last]


def summarize(planes: list) -> Summary | None:
    """The reduced numbers, or None where the trace holds no window span
    or no device op."""
    spans = [ev for name, lines in planes if not DEVICE_PLANE.match(name)
             for evs in lines.values() for ev in evs]
    windows = [ev for ev in spans if ev[0] == WINDOW_SPAN]
    if not windows:
        return None
    w0 = min(s for _, s, _ in windows)
    w1 = max(s + d for _, s, d in windows)
    inner = [ev for ev in spans if ev[0] != WINDOW_SPAN]
    devices = [(n, lines) for n, lines in planes if DEVICE_PLANE.match(n)]
    busy = launches = 0.0
    ops: dict[str, float] = {}
    gaps: dict[str, list] = {}
    n_dev = 0
    for _, lines in devices:
        evs = lines.get(OP_LINE) or lines.get(MODULE_LINE) or []
        evs = [ev for ev in evs if ev[1] < w1 and ev[1] + ev[2] > w0]
        if not evs:
            continue
        n_dev += 1
        modules = sorted(lines.get(MODULE_LINE, []), key=lambda ev: ev[1])
        launches += sum(1 for _, s, _ in modules if w0 <= s < w1)
        st = np.array([max(s, w0) for _, s, _ in evs], dtype=np.float64)
        en = np.array([min(s + d, w1) for _, s, d in evs], dtype=np.float64)
        for name, a, b in zip(_in_module(evs, modules), st, en):
            ops[name] = ops.get(name, 0.0) + (b - a)
        us, ue = _union(st, en)
        busy += float((ue - us).sum())
        g0 = np.r_[w0, ue]
        g1 = np.r_[us, w1]
        keep = g1 > g0
        for a, b in zip(g0[keep], g1[keep]):
            label = _label(inner, (a + b) / 2)
            slot = gaps.setdefault(label, [0.0, 0])
            slot[0] += b - a
            slot[1] += 1
    if n_dev == 0:
        return None
    top_ops = sorted(ops.items(), key=lambda kv: -kv[1])[:TOP]
    top_gaps = sorted(gaps.items(), key=lambda kv: -kv[1][0])[:TOP]
    return Summary(
        window_s=(w1 - w0) * 1e-9, busy_s=busy / n_dev * 1e-9,
        launches=launches / n_dev, devices=n_dev,
        device_ops=[[n, t / n_dev * 1e-9] for n, t in top_ops],
        idle_gaps=[[f"{label} x{c // n_dev}", t / n_dev * 1e-9]
                   for label, (t, c) in top_gaps])


def _in_module(evs: list, modules: list) -> list:
    """Each op's name prefixed with the program it ran in (``jit_step``,
    ``jit_assemble``, ...), cut to ``NAME_CHARS``."""
    starts = np.array([s for _, s, _ in modules], dtype=np.int64)
    at = np.array([s for _, s, _ in evs], dtype=np.int64)
    idx = np.searchsorted(starts, at, side="right") - 1
    out = []
    for (name, s, _), i in zip(evs, idx.tolist()):
        mod = ""
        if i >= 0 and s < modules[i][1] + modules[i][2]:
            mod = modules[i][0].split("(")[0] + ": "
        out.append((mod + name)[:NAME_CHARS])
    return out


def _label(spans: list, t: float) -> str:
    """The innermost (shortest) harness span open at time ``t``."""
    best, best_d = WINDOW_SPAN, None
    for name, s, d in spans:
        if s <= t < s + d and (best_d is None or d < best_d):
            best, best_d = name, d
    return best
