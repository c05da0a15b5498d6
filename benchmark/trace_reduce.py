"""Reduce a profiler trace (``.xplane.pb``) to the benchmark's numbers.

* The window: the host span named ``WINDOW`` that the harness writes
  around its measured window.  Everything below is clipped to it.
* Device busy time: per device, the union of the intervals of the
  operations on its ``XLA Ops`` line; ``busy_s`` averages it over the
  devices, and the idle share is one minus busy over the window.
* Device time per operation and per program (``XLA Modules`` line),
  summed by name, for the readers that match names.
* Idle gaps on the first device, each put down to the innermost host
  span that covers its middle (``NO_SPAN`` where none does).
* Host spans on the same clock as the device events.

A trace with no device plane (a CPU run) gives ``devices == 0`` and no
device numbers.
"""

import bisect
import dataclasses
import glob
import os
import re
from collections import defaultdict

WINDOW = "bench.window"
#: the owner of an idle gap that no harness span covers
NO_SPAN = "host idle"
#: device planes, as the profiler names them
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    devices: int
    busy_s: float                      # averaged over devices
    op_s: dict                         # operation name -> device s
    module_s: dict                     # program name -> device s
    gaps: list                         # (start ns, end ns) on device 0
    gap_s_by_span: dict                # host span name -> idle s
    spans: list                        # (name, start ns, end ns)

    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def time_matching(self, patterns) -> float:
        """Device seconds of the programs whose name contains any of
        ``patterns``."""
        return sum(s for name, s in self.module_s.items()
                   if any(p in name for p in patterns))


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals) -> list:
    """Merged, sorted, non-overlapping (start, end) intervals."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(i) for i in out]


def attribute(gaps, spans) -> list:
    """For each gap, the name of the innermost span that covers its
    middle, or ``NO_SPAN``."""
    mids = [(s + e) / 2 for s, e in gaps]
    order = sorted(range(len(mids)), key=mids.__getitem__)
    sorted_mids = [mids[i] for i in order]
    owner = [NO_SPAN] * len(gaps)
    # widest first, so an inner span overwrites the one around it
    for name, s, e in sorted(spans, key=lambda sp: sp[1] - sp[2]):
        lo = bisect.bisect_left(sorted_mids, s)
        hi = bisect.bisect_right(sorted_mids, e)
        for k in range(lo, hi):
            owner[order[k]] = name
    return owner


def _clip(s, e, w0, w1):
    return max(s, w0), min(e, w1)


def reduce_profile(profile) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData``."""
    spans, device_lines = [], {}
    for plane in profile.planes:
        if DEVICE_PLANE.match(plane.name):
            device_lines[plane.name] = {line.name: list(line.events)
                                        for line in plane.lines}
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        spans.append((ev.name, ev.start_ns, ev.end_ns))
    windows = [(s, e) for n, s, e in spans if n == WINDOW]
    if not windows:
        raise ValueError(f"the trace holds no {WINDOW!r} span")
    w0, w1 = windows[0]
    op_s, module_s = defaultdict(float), defaultdict(float)
    busy, gaps = [], []
    for i, name in enumerate(sorted(device_lines)):
        lines = device_lines[name]
        intervals = []
        for ev in lines.get(OPS_LINE, ()):
            s, e = _clip(ev.start_ns, ev.end_ns, w0, w1)
            if e > s:
                intervals.append((s, e))
                op_s[ev.name] += (e - s) / 1e9
        for ev in lines.get(MODULES_LINE, ()):
            s, e = _clip(ev.start_ns, ev.end_ns, w0, w1)
            if e > s:
                module_s[ev.name] += (e - s) / 1e9
        merged = union(intervals)
        busy.append(sum(e - s for s, e in merged) / 1e9)
        if i == 0:
            edges = [w0] + [x for iv in merged for x in iv] + [w1]
            gaps = [(edges[k], edges[k + 1])
                    for k in range(0, len(edges), 2)
                    if edges[k + 1] > edges[k]]
    gap_s = defaultdict(float)
    owner = attribute(gaps, [sp for sp in spans if sp[0] != WINDOW])
    for (s, e), who in zip(gaps, owner):
        gap_s[who] += (e - s) / 1e9
    n_dev = len(device_lines)
    return TraceSummary(
        window_s=(w1 - w0) / 1e9, devices=n_dev,
        busy_s=sum(busy) / n_dev if n_dev else 0.0,
        op_s=dict(op_s), module_s=dict(module_s), gaps=gaps,
        gap_s_by_span=dict(gap_s), spans=spans)


def reduce_file(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    return reduce_profile(ProfileData.from_file(path))


def breakdown(summary: TraceSummary, top: int = 10) -> dict:
    """The result line's ``breakdown``: the device operations that took
    most time, and the idle time by what the host was doing."""
    ops = sorted(summary.op_s.items(), key=lambda kv: -kv[1])[:top]
    idle = sorted(summary.gap_s_by_span.items(), key=lambda kv: -kv[1])
    return {"device_ops": [[n, s] for n, s in ops],
            "idle_gaps": [[n, s] for n, s in idle[:top]]}
