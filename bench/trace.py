"""Reduce a profiler trace (`.xplane.pb`) to device metrics.

- busy: the union of the intervals in which an operation ran on a device
  (line "XLA Ops" of each TPU plane), clipped to the traced window and
  averaged over the chips used; the idle share is 1 - busy / window;
- device time of each compiled program (line "XLA Modules"), by its name
  without the trailing run id, with its number of calls;
- the breakdown: the device operations that took the most time (loops
  and calls left out: their spans hold the ops of their bodies), and the
  longest idle gaps, each named by the innermost `bench.*` host span that
  was open at the gap's middle ("other" where none was).

The traced window is the host span `bench.window`.
"""
from __future__ import annotations

import glob
import gzip
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

WINDOW_SPAN = "bench.window"
_RUN_ID = re.compile(r"\(\d+\)$")
_LAYOUT = re.compile(r"\{[^{}]*\}")
_RESULT = re.compile(r"^(\(.*?\)|\S+)\s+([\w.\-]+)\(")


def op_name(text: str) -> str:
    """An XLA op event's name without operands and layouts:
    '%fusion.3 = bf16[8,128]{1,0} fusion(...), ...' -> 'fusion.3 =
    bf16[8,128] fusion'."""
    if " = " not in text:
        return text
    lhs, rhs = text.split(" = ", 1)
    m = _RESULT.match(_LAYOUT.sub("", rhs))
    rhs = f"{m.group(1)[:60]} {m.group(2)}" if m else rhs[:80]
    return f"{lhs.lstrip('%')} = {rhs}"


def _container(text: str) -> bool:
    """A loop or call op, whose span holds the ops of its body."""
    m = _RESULT.match(_LAYOUT.sub("", text.split(" = ", 1)[-1]))
    return bool(m) and m.group(2) in ("while", "conditional", "call")


@dataclass
class Reduced:
    window_s: float
    busy_s: float                     # mean over the chips
    chips: int
    programs: dict = field(default_factory=dict)   # name -> [seconds, calls]
    device_ops: list = field(default_factory=list)  # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)   # [[host span, seconds]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s

    def program(self, pattern: str):
        """(seconds, calls) summed over programs whose name matches
        `pattern` (a regular expression), or None when none ran."""
        hits = [v for k, v in self.programs.items() if re.search(pattern, k)]
        if not hits:
            return None
        return sum(v[0] for v in hits), sum(v[1] for v in hits)


def find_xplane(trace_dir: str) -> str:
    paths = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb*"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return max(paths, key=os.path.getmtime)


def load(path: str):
    from jax.profiler import ProfileData
    data = open(path, "rb").read()
    if path.endswith(".gz"):
        data = gzip.decompress(data)
    return ProfileData.from_serialized_xspace(data)


def _union(iv: list) -> list:
    out = []
    for a, b in sorted(iv):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def _clip(iv, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in iv if b > lo and a < hi]


def reduce(profile, top: int = 10) -> Reduced:
    host_spans, devices = [], []
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench."):
                        host_spans.append((ev.start_ns, ev.end_ns, ev.name))
        elif re.match(r"/device:TPU:\d+$", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            devices.append(lines)
    windows = [s for s in host_spans if s[2] == WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {WINDOW_SPAN} span in the trace")
    lo, hi = windows[0][0], windows[0][1]
    if not devices:
        raise ValueError("no TPU device plane in the trace")
    programs = defaultdict(lambda: [0.0, 0])
    op_time = defaultdict(float)
    busy_total = 0.0
    merged0 = None
    for lines in devices:
        ops = lines.get("XLA Ops")
        iv = [] if ops is None else [(e.start_ns, e.end_ns)
                                     for e in ops.events]
        merged = _union(_clip(iv, lo, hi))
        busy_total += sum(b - a for a, b in merged)
        if merged0 is None:
            merged0 = merged
            for e in (ops.events if ops is not None else ()):
                a, b = max(e.start_ns, lo), min(e.end_ns, hi)
                if b > a and not _container(e.name):
                    op_time[op_name(e.name)] += (b - a) * 1e-9
            mods = lines.get("XLA Modules")
            for e in (mods.events if mods is not None else ()):
                if lo <= e.start_ns < hi:
                    p = programs[_RUN_ID.sub("", e.name)]
                    p[0] += e.duration_ns * 1e-9
                    p[1] += 1
    gaps = defaultdict(float)
    edges = [lo] + [x for ab in merged0 for x in ab] + [hi]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b > a:
            mid = (a + b) / 2
            inner = [s for s in host_spans
                     if s[0] <= mid <= s[1] and s[2] != WINDOW_SPAN]
            name = min(inner, key=lambda s: s[1] - s[0])[2] if inner \
                else "other"
            gaps[name] += (b - a) * 1e-9
    rank = lambda d: [[k, v] for k, v in
                      sorted(d.items(), key=lambda kv: -kv[1])[:top]]
    return Reduced(window_s=(hi - lo) * 1e-9,
                   busy_s=busy_total * 1e-9 / len(devices),
                   chips=len(devices), programs=dict(programs),
                   device_ops=rank(op_time), idle_gaps=rank(gaps))
