"""Reduction of a profiler trace to the benchmark's device numbers.

Input: the ``.xplane.pb`` that ``jax.profiler`` writes.  The harness marks
the measured window with a ``TraceAnnotation`` (`WINDOW`) and the layer
calls with theirs (`chipbench.spans`); all of them sit on host threads of
the trace, on the same clock as the device's operations.

* busy time: the union of the intervals in which an operation ran on a
  device, clipped to the window, averaged over the devices;
* idle gaps: the complement of that union on the first device, each
  labelled by the host span that covers most of it (``"none"`` if none);
* operation time: the *self* time of each device operation, by name — its
  duration less that of the operations nested inside it (a ``while`` and
  the ops of its body sit on the same line), so that times add up.

Device operations are the events of the ``XLA Ops`` line of every
``/device:TPU:<n>`` plane; `reduce` takes other selectors for tests.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from collections import defaultdict
from typing import Callable, Iterable, Optional

WINDOW = "chipbench_window"


def tpu_op_line(plane_name: str, line_name: str) -> bool:
    return plane_name.startswith("/device:TPU:") and line_name == "XLA Ops"


@dataclasses.dataclass
class Summary:
    window: tuple                 # (start_ns, end_ns)
    busy_ns: float                # mean over devices
    n_devices: int
    gaps: list                    # [(label, start_ns, dur_ns)], longest first
    op_ns: dict                   # name -> summed ns (all devices, clipped)
    spans: list                   # [(name, start_ns, end_ns)] host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        return self.busy_ns / 1e9

    def op_seconds(self, match: Callable[[str], bool]) -> float:
        """Device seconds, per device, of the operations ``match`` names."""
        ns = sum(v for k, v in self.op_ns.items() if match(k))
        return ns / 1e9 / max(self.n_devices, 1)

    def top_ops(self, n: int = 10) -> list:
        per_dev = max(self.n_devices, 1)
        items = sorted(self.op_ns.items(), key=lambda kv: -kv[1])[:n]
        return [[short_name(k), v / 1e9 / per_dev] for k, v in items]

    def top_gaps(self, n: int = 10) -> list:
        return [[label, dur / 1e9] for label, _, dur in self.gaps[:n]]


def short_name(op: str) -> str:
    """``%fusion.3 = f32[8,128]{...} fusion(...), ...`` → ``%fusion.3
    fusion f32[8,128]{...}``: the instruction, its opcode and the start of
    its result shape, instead of the whole HLO line."""
    m = re.match(r"(%[\w.\-]+) = (.*?) ([a-z][\w\-]*)\(", op)
    return f"{m.group(1)} {m.group(3)} {m.group(2)[:48]}" if m else op[:96]


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    if len(paths) != 1:
        raise FileNotFoundError(f"expected one trace under {log_dir}, "
                                f"found {paths}")
    return paths[0]


def union(intervals: Iterable[tuple]) -> list:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [tuple(x) for x in out]


def complement(busy: list, lo: float, hi: float) -> list:
    gaps, cur = [], lo
    for s, e in busy:
        if s > cur:
            gaps.append((cur, s))
        cur = max(cur, e)
    if cur < hi:
        gaps.append((cur, hi))
    return gaps


def self_times(events: list) -> list:
    """``[(start, end, name)]`` of one line → ``[(name, self_ns)]``: each
    event's duration less its direct children's (events nest)."""
    out, stack = [], []            # stack: [name, end, self]
    for s, e, n in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][1] <= s:
            top = stack.pop()
            out.append((top[0], top[2]))
        if stack:
            stack[-1][2] -= e - s
        stack.append([n, e, e - s])
    out.extend((n, d) for n, _, d in stack)
    return out


def label_of(gap: tuple, spans: list) -> str:
    """The host span that covers most of ``gap``."""
    best, best_cover = "none", 0.0
    for name, s, e in spans:
        cover = min(e, gap[1]) - max(s, gap[0])
        if cover > best_cover:
            best, best_cover = name, cover
    return best


def reduce(path: str, span_names: Iterable[str],
           is_op_line: Callable[[str, str], bool] = tpu_op_line,
           ) -> Optional[Summary]:
    """Reduce the trace at ``path``; None when it holds no window mark."""
    import jax
    span_names = set(span_names)
    data = jax.profiler.ProfileData.from_file(path)
    window = None
    spans: list = []
    per_device: dict = defaultdict(list)
    for plane in data.planes:
        for line in plane.lines:
            op_line = is_op_line(plane.name, line.name)
            for ev in line.events:
                s = ev.start_ns
                e = s + ev.duration_ns
                if op_line:
                    if ev.duration_ns > 0:
                        per_device[plane.name].append((s, e, ev.name))
                elif ev.name == WINDOW:
                    window = (s, e)
                elif ev.name in span_names:
                    spans.append((ev.name, s, e))
    if window is None:
        return None
    lo, hi = window
    busy_total = 0.0
    op_ns: dict = defaultdict(float)
    gaps: list = []
    for i, dev in enumerate(sorted(per_device)):
        clipped = [(max(s, lo), min(e, hi), n) for s, e, n in per_device[dev]
                   if e > lo and s < hi]
        for n, d in self_times(clipped):
            op_ns[n] += d
        busy = union((s, e) for s, e, _ in clipped)
        busy_total += sum(e - s for s, e in busy)
        if i == 0:
            gaps = [(label_of(g, spans), g[0], g[1] - g[0])
                    for g in complement(busy, lo, hi)]
    gaps.sort(key=lambda g: -g[2])
    n_dev = len(per_device)
    return Summary(window=window, busy_ns=busy_total / max(n_dev, 1),
                   n_devices=n_dev, gaps=gaps, op_ns=dict(op_ns),
                   spans=[s for s in spans if s[2] > lo and s[1] < hi])
