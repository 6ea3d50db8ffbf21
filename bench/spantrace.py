"""The server's own host spans (`omni.*`) in a traced run's profile.

`bench/run.py --trace 1` leaves its profile under `.bench_trace/` and
reduces it with `bench/trace.py`, which reads the `bench.*` spans only.
This module reads the same profile for the spans the serving loop opens
(`src/repro/serving/spans.py`):

- `spans`: for each span name, [seconds, count, idle seconds]: its time
  inside the traced window (`bench.window`), the number of spans that
  overlap the window, and how long the device sat idle inside them,
  children included (the first chip, as in `bench/trace.py`'s gaps);
- `idle_gaps`: every idle gap of the window named by the innermost span,
  `bench.*` or `omni.*`, open at its middle ("other" where none was);
- `picked`: for each request id, when a prefill round first picked it,
  in seconds from the window's start: the start of the first span that
  carries its `rid` inside an `omni.prefill.round`, a resume (a task
  that has not started) or a chunk (one resumed when it was queued). A
  request whose whole prompt was resumed when it was queued opens neither
  and is left out.

A profile with no `omni.*` span gives empty `spans` and `picked`.

    python3 bench/spantrace.py [trace dir]

prints the reduction of the newest profile under the directory
(`.bench_trace/` by default) as one JSON line.
"""
from __future__ import annotations

import bisect
import functools
import json
import os
import re
import sys
from collections import defaultdict
from dataclasses import dataclass, field
from pathlib import Path

if __package__ in (None, ""):
    sys.path.insert(0, str(Path(__file__).resolve().parent.parent))

from bench import trace  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
TRACE_DIR = ROOT / ".bench_trace"          # where bench/run.py traces
PREFIX = "omni."
ROUND = "omni.prefill.round"
PICKS = ("omni.prefill.resume", "omni.prefill.chunk")


@dataclass
class SpanTrace:
    window_s: float
    spans: dict = field(default_factory=dict)      # name -> [s, count, idle]
    idle_gaps: list = field(default_factory=list)  # [[span, seconds]]
    picked: dict = field(default_factory=dict)     # rid -> s from the start


class _Busy:
    """Busy time of a sorted list of disjoint [a, b) intervals, by
    prefix sums."""

    def __init__(self, merged: list):
        self.starts = [a for a, _ in merged]
        self.ends = [b for _, b in merged]
        self.cum = [0]
        for a, b in merged:
            self.cum.append(self.cum[-1] + b - a)

    def before(self, t) -> float:
        k = bisect.bisect_right(self.starts, t) - 1
        if k < 0:
            return 0
        return self.cum[k] + min(t, self.ends[k]) - self.starts[k]

    def within(self, a, b) -> float:
        return self.before(b) - self.before(a)


def _rid(ev):
    for name, value in ev.stats:
        if name == "rid":
            return int(value)
    return None


def _innermost(spans: list, points: list) -> list:
    """For each of the ascending `points`, the shortest of `spans` (start,
    end, name) that holds it, or None."""
    order = sorted(spans, key=lambda s: s[0])
    out, active, k = [], [], 0
    for p in points:
        while k < len(order) and order[k][0] <= p:
            active.append(order[k])
            k += 1
        active = [s for s in active if s[1] >= p]
        out.append(min(active, key=lambda s: s[1] - s[0]) if active
                   else None)
    return out


def reduce(profile, top: int = 10) -> SpanTrace:
    host, picks, ops = [], [], None
    for plane in profile.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(("bench.", PREFIX)):
                        host.append((ev.start_ns, ev.end_ns, ev.name))
                        if ev.name in PICKS:
                            picks.append((ev.start_ns, _rid(ev)))
        elif ops is None and re.match(r"/device:TPU:\d+$", plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            ops = lines.get("XLA Ops")
            ops = [] if ops is None else [(e.start_ns, e.end_ns)
                                          for e in ops.events]
    windows = [s for s in host if s[2] == trace.WINDOW_SPAN]
    if not windows:
        raise ValueError(f"no {trace.WINDOW_SPAN} span in the trace")
    if ops is None:
        raise ValueError("no TPU device plane in the trace")
    lo, hi = windows[0][0], windows[0][1]
    merged = trace._union(trace._clip(ops, lo, hi))
    busy = _Busy(merged)
    spans = defaultdict(lambda: [0.0, 0, 0.0])
    for a, b, name in host:
        if not name.startswith(PREFIX):
            continue
        a, b = max(a, lo), min(b, hi)
        if b > a:
            s = spans[name]
            s[0] += (b - a) * 1e-9
            s[1] += 1
            s[2] += (b - a - busy.within(a, b)) * 1e-9
    edges = [lo] + [x for ab in merged for x in ab] + [hi]
    gaps = [(a, b) for a, b in zip(edges[0::2], edges[1::2]) if b > a]
    named = defaultdict(float)
    inner = _innermost([s for s in host if s[2] != trace.WINDOW_SPAN],
                       [(a + b) / 2 for a, b in gaps])
    for (a, b), s in zip(gaps, inner):
        named["other" if s is None else s[2]] += (b - a) * 1e-9
    rounds = sorted((a, b) for a, b, name in host if name == ROUND)
    round_starts = [a for a, _ in rounds]
    picked = {}
    for t, rid in sorted(picks):
        k = bisect.bisect_right(round_starts, t) - 1
        if rid is not None and rid not in picked and k >= 0 \
                and t <= rounds[k][1]:
            picked[rid] = (t - lo) * 1e-9
    rank = sorted(named.items(), key=lambda kv: -kv[1])[:top]
    return SpanTrace(window_s=(hi - lo) * 1e-9, spans=dict(spans),
                     idle_gaps=[[k, v] for k, v in rank], picked=picked)


@functools.lru_cache(maxsize=2)
def _reduce_file(path: str, mtime: float) -> SpanTrace:
    return reduce(trace.load(path))


def of(run, trace_dir=TRACE_DIR):
    """The reduction of a traced run's profile (read once), or None for an
    untraced run or where the profile found is not this run's."""
    if run.trace is None:
        return None
    try:
        path = trace.find_xplane(str(trace_dir))
    except FileNotFoundError:
        return None
    st = _reduce_file(path, os.path.getmtime(path))
    if abs(st.window_s - run.trace.window_s) > 1e-6:
        return None
    return st


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    path = trace.find_xplane(argv[0] if argv else str(TRACE_DIR))
    st = reduce(trace.load(path))
    print(json.dumps({"window_s": st.window_s, "spans": st.spans,
                      "idle_gaps": st.idle_gaps,
                      "picked": len(st.picked)}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
