"""Reduction of a profiler trace to the per-layer metrics' raw readings.

``load`` reads the ``.xplane.pb`` that ``jax.profiler`` wrote and keeps
three kinds of events, as (kind, chip, name, start_ns, end_ns):

  op      an operation on a device's "XLA Ops" line, named by its HLO
          instruction (``%fusion.12``, ``%sign_pack.1``: a Pallas call
          takes its kernel's name); the ops a loop or a conditional
          runs are nested inside that op's own event
  module  a program execution on a device's "XLA Modules" line
  host    one of the benchmark's own host spans (``bench.*``)

Everything else is dropped.  ``Context`` then answers what the metric
readers ask, all within the traced window (the ``bench.window`` span):
device busy time as the union of op intervals, idle gaps named by the
host span that covers most of each, summed op time by name pattern, and
the device time of each step's program.
"""
from __future__ import annotations

import glob
import gzip
import json
import os
import re

DEVICE_PLANE = re.compile(r"/device:TPU:(\d+)$")
HOST_PREFIX = "bench."


def load(trace_dir: str) -> list:
    """Events of the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    data = ProfileData.from_file(max(paths, key=os.path.getmtime))
    events = []
    for plane in data.planes:
        m = DEVICE_PLANE.search(plane.name)
        for line in plane.lines:
            if m and line.name in ("XLA Ops", "XLA Modules"):
                kind = "op" if line.name == "XLA Ops" else "module"
                chip = int(m.group(1))
                events.extend((kind, chip, e.name.split(" = ", 1)[0],
                               e.start_ns, e.end_ns) for e in line.events)
            elif not m:
                events.extend(("host", -1, e.name, e.start_ns, e.end_ns)
                              for e in line.events
                              if e.name.startswith(HOST_PREFIX))
    return events


def read_saved(path: str) -> list:
    """Events kept as gzipped JSON (the recorded trace of the tests)."""
    with gzip.open(path, "rt") as f:
        return [tuple(e) for e in json.load(f)]


def union(intervals: list) -> list:
    """Sorted, merged [start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return out


class Context:
    """What the metric readers read of one traced window."""

    def __init__(self, events: list, *, chips: int, steps: int, t_e: int,
                 tokens_per_step: int, cell=None, peaks=None,
                 n_pad: int | None = None):
        self.chips, self.steps, self.t_e = chips, steps, t_e
        self.cell, self.peaks, self.n_pad = cell, peaks, n_pad
        wins = [e for e in events if e[0] == "host" and e[2] == "bench.window"]
        if not wins:
            raise ValueError("the trace holds no bench.window span")
        self.w0, self.w1 = wins[-1][3], wins[-1][4]
        clip = lambda e: (e[0], e[1], e[2], max(e[3], self.w0),
                          min(e[4], self.w1))
        inside = [clip(e) for e in events if e[4] > self.w0 and e[3] < self.w1]
        self.ops = [e for e in inside if e[0] == "op" and e[4] > e[3]]
        self.modules = sorted((e for e in inside if e[0] == "module"),
                              key=lambda e: e[3])
        self.host = [e for e in inside if e[0] == "host"
                     and e[2] != "bench.window"]
        self.window_s = (self.w1 - self.w0) * 1e-9
        self.tokens_per_s = steps * tokens_per_step / self.window_s
        self._busy = {c: union([(e[3], e[4]) for e in self.ops if e[1] == c])
                      for c in sorted({e[1] for e in self.ops})}

    @property
    def busy_s(self) -> float:
        """Device busy seconds, averaged over the cell's chips."""
        tot = sum(e - s for iv in self._busy.values() for s, e in iv)
        return tot * 1e-9 / self.chips

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def op_seconds(self, pattern: str) -> float:
        """Device seconds of the ops whose name matches ``pattern``
        (a regular expression), averaged over the cell's chips."""
        rx = re.compile(pattern, re.IGNORECASE)
        tot = sum(e[4] - e[3] for e in self.ops if rx.search(e[2]))
        return tot * 1e-9 / self.chips

    def self_times(self) -> dict:
        """Seconds of each op name with the ops nested inside it taken
        out (a loop's own time is what its body's ops leave), averaged
        over the cell's chips."""
        by = {}
        for chip in self._busy:
            stack = []                      # [end, name, child time]
            evs = sorted((e for e in self.ops if e[1] == chip),
                         key=lambda e: (e[3], -e[4]))
            for _, _, name, s, e in evs + [("op", chip, None, 1 << 62,
                                             1 << 62)]:
                while stack and stack[-1][0] <= s:
                    end, nm, start, child = stack.pop()
                    own = end - start - child
                    by[nm] = by.get(nm, 0) + own
                    if stack:
                        stack[-1][3] += end - start
                if name is not None:
                    stack.append([e, name, s, 0])
        return {k: v * 1e-9 / self.chips for k, v in by.items()}

    def top_ops(self, n: int) -> list:
        """The ``n`` op names with the most self time, digits of the
        instruction number dropped (``%fusion.12`` counts as ``%fusion``)
        so that repeated instances add up."""
        by = {}
        for k, v in self.self_times().items():
            key = re.sub(r"\.\d+$", "", k)
            by[key] = by.get(key, 0.0) + v
        return [[k, v] for k, v in sorted(by.items(),
                                          key=lambda kv: -kv[1])[:n]]

    def gaps(self, chip: int | None = None) -> list:
        """(start, end) of each idle stretch of a chip within the window."""
        chip = min(self._busy) if chip is None else chip
        iv = self._busy.get(chip, [])
        out, t = [], self.w0
        for s, e in iv:
            if s > t:
                out.append((t, s))
            t = max(t, e)
        if t < self.w1:
            out.append((t, self.w1))
        return out

    def span_over(self, s: int, e: int) -> str:
        """The host span that covers most of [s, e), else "other"."""
        best, name = 0, "other"
        for h in self.host:
            ov = min(e, h[4]) - max(s, h[3])
            if ov > best:
                best, name = ov, h[2]
        return name

    def idle_gaps(self, n: int) -> list:
        gaps = sorted(self.gaps(), key=lambda g: g[0] - g[1])[:n]
        return [[self.span_over(s, e), (e - s) * 1e-9] for s, e in gaps]

    def step_seconds(self, pattern: str = r"train_step") -> list:
        """Device seconds of each step program's execution, in order
        (from the first chip that ran it)."""
        rx = re.compile(pattern)
        mods = [e for e in self.modules if rx.search(e[2])]
        if not mods:
            return []
        chip = min(e[1] for e in mods)
        return [(e[4] - e[3]) * 1e-9 for e in mods if e[1] == chip]

    def boundary_split(self):
        """(boundary step seconds, local step seconds): the window starts
        at a round boundary, so step i is one when i % T_E == 0."""
        secs = self.step_seconds()
        if len(secs) != self.steps:
            return [], []
        return ([s for i, s in enumerate(secs) if i % self.t_e == 0],
                [s for i, s in enumerate(secs) if i % self.t_e])
