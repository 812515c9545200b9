"""From a profiler trace (.xplane.pb) to device busy time, per-operation
time and idle gaps.

Two stages, so that the second can be checked on a small recorded trace
(bench/fixtures/) with no profiler and no chip:

1. ``load_xplane(path)`` reads the file with ``jax.profiler.ProfileData``
   into plain data: ``{"planes": [{"name", "lines": [{"name", "events":
   [[name, start_ns, dur_ns], ...]}]}]}``.
2. ``reduce(trace, ...)`` picks the device planes and the line that holds
   single operations, keeps the *leaf* events (an operation that contains
   others on the same line, such as the ``while`` of a scanned chunk, is
   a frame and no work of its own), and gives the union of their
   intervals (busy), their durations by name, and the gaps between them
   with what the host's annotated spans were doing meanwhile.

Times are nanoseconds as the profiler gives them; results are seconds.
"""

from __future__ import annotations

import glob
import os
import re
from typing import Any, Dict, Iterable, List, Optional, Sequence, Tuple

DEVICE_PLANE = r"^/device:TPU:\d+$"
OPS_LINE = r"^XLA Ops$"
HOST_PLANE = r"^/host:CPU$"


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def load_xplane(path: str, keep_plane: str = r"^/(device|host):") -> Dict:
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        if not re.search(keep_plane, plane.name):
            continue
        lines = []
        for line in plane.lines:
            events = [[ev.name, int(ev.start_ns), int(ev.duration_ns)]
                      for ev in line.events]
            lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def short_name(name: str) -> str:
    """An XLA operation's event name is its whole HLO line; the part
    before " = " is the operation's own name."""
    return name.split(" = ", 1)[0][:120]


def leaf_events(events: Sequence[Sequence]) -> List[Tuple[str, int, int]]:
    """Events of one line that contain no other event of that line."""
    evs = sorted(((int(s), -int(d), n) for n, s, d in events))
    out: List[Tuple[str, int, int]] = []
    stack: List[List] = []      # [end, name, start, has_child]
    for s, negd, n in evs:
        e = s - negd
        # what has ended, and what this event overlaps without lying
        # inside it (a neighbour, not a frame), is closed first
        while stack and (stack[-1][0] <= s or stack[-1][0] < e):
            end, name, start, has_child = stack.pop()
            if not has_child:
                out.append((name, start, end - start))
        if stack:
            stack[-1][3] = True
        stack.append([e, n, s, False])
    while stack:
        end, name, start, has_child = stack.pop()
        if not has_child:
            out.append((name, start, end - start))
    out.sort(key=lambda t: t[1])
    return out


def union_ns(intervals: Iterable[Tuple[int, int]]) -> int:
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


class DeviceTrace:
    """The reduction of one device plane inside a window."""

    def __init__(self, plane: str, leaves: List[Tuple[str, int, int]],
                 window: Tuple[int, int]):
        self.plane = plane
        self.window = window
        w0, w1 = window
        self.leaves = [(n, max(s, w0), min(s + d, w1) - max(s, w0))
                       for n, s, d in leaves if s + d > w0 and s < w1]
        self.busy_ns = union_ns((s, s + d) for _, s, d in self.leaves)

    def by_name(self) -> Dict[str, int]:
        out: Dict[str, int] = {}
        for n, _, d in self.leaves:
            n = short_name(n)
            out[n] = out.get(n, 0) + d
        return out

    def matched_ns(self, patterns: Sequence[str], invert: bool = False
                   ) -> Optional[int]:
        """Summed duration of the leaf operations whose name matches any
        pattern (or none of them, with ``invert``). None if the plane
        holds no operation at all."""
        if not self.leaves:
            return None
        rx = [re.compile(p) for p in patterns]
        total = 0
        for n, _, d in self.leaves:
            hit = any(r.search(n) for r in rx)
            if hit != invert:
                total += d
        return total

    def gaps(self) -> List[Tuple[int, int]]:
        """(start, length) of every interval of the window in which no
        leaf operation runs, longest first."""
        out = []
        cur = self.window[0]
        for _, s, d in sorted(self.leaves, key=lambda t: t[1]):
            if s > cur:
                out.append((cur, s - cur))
            cur = max(cur, s + d)
        if self.window[1] > cur:
            out.append((cur, self.window[1] - cur))
        out.sort(key=lambda t: -t[1])
        return out


class TraceReduction:
    def __init__(self, devices: List[DeviceTrace],
                 host_spans: List[Tuple[str, int, int]],
                 window: Tuple[int, int]):
        self.devices = devices
        self.host_spans = host_spans
        self.window = window

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    @property
    def busy_s(self) -> float:
        """Busy seconds averaged over the device planes."""
        if not self.devices:
            return 0.0
        return sum(d.busy_ns for d in self.devices) / len(self.devices) / 1e9

    def matched_s(self, patterns: Sequence[str], invert: bool = False
                  ) -> Optional[float]:
        vals = [d.matched_ns(patterns, invert) for d in self.devices]
        vals = [v for v in vals if v is not None]
        if not vals:
            return None
        return sum(vals) / len(vals) / 1e9

    def top_ops(self, k: int = 10) -> List[List]:
        tot: Dict[str, int] = {}
        for d in self.devices:
            for n, v in d.by_name().items():
                tot[n] = tot.get(n, 0) + v
        nd = max(len(self.devices), 1)
        top = sorted(tot.items(), key=lambda t: -t[1])[:k]
        return [[n, v / nd / 1e9] for n, v in top]

    def top_gaps(self, k: int = 5) -> List[List]:
        """The longest idle gaps of the first device, each named by the
        annotated host span that covers most of it."""
        if not self.devices:
            return []
        out = []
        for s, length in self.devices[0].gaps()[:k]:
            best, cover = "unattributed", 0
            for n, hs, hd in self.host_spans:
                ov = min(s + length, hs + hd) - max(s, hs)
                if ov > cover:
                    best, cover = n, ov
            out.append([best, length / 1e9])
        return out


def reduce(trace: Dict, span_prefix: str = "bench:",
           window_span: Optional[str] = None,
           device_plane: str = DEVICE_PLANE, ops_line: str = OPS_LINE,
           host_plane: str = HOST_PLANE) -> TraceReduction:
    """``window_span`` names the host annotation whose interval is the
    traced window; without it the window is the extent of the device
    operations."""
    host_spans: List[Tuple[str, int, int]] = []
    for plane in trace["planes"]:
        if not re.search(host_plane, plane["name"]):
            continue
        for line in plane["lines"]:
            for n, s, d in line["events"]:
                if n.startswith(span_prefix):
                    host_spans.append((n, int(s), int(d)))
    per_plane = []
    for plane in trace["planes"]:
        if not re.search(device_plane, plane["name"]):
            continue
        for line in plane["lines"]:
            if re.search(ops_line, line["name"]):
                per_plane.append((plane["name"], leaf_events(line["events"])))
    window = None
    if window_span is not None:
        spans = [(s, s + d) for n, s, d in host_spans if n == window_span]
        if spans:
            window = (min(s for s, _ in spans), max(e for _, e in spans))
    if window is None:
        starts = [s for _, lv in per_plane for _, s, _ in lv]
        ends = [s + d for _, lv in per_plane for _, s, d in lv]
        window = (min(starts), max(ends)) if starts else (0, 0)
    devices = [DeviceTrace(name, lv, window) for name, lv in per_plane]
    return TraceReduction(devices, host_spans, window)
