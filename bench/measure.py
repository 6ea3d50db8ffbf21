"""Metric arithmetic: end-to-end metrics from the window's tracks, and the
record of a run that per-layer readers take their numbers from."""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import numpy as np


def percentile(values, q: float) -> Optional[float]:
    return float(np.percentile(np.asarray(values, float), q)) if len(values) \
        else None


def ttft(due: list, t_end: float) -> tuple[list, int]:
    """(time to first token of every request due in the window, misses):
    a request with no first token by the window's end counts at the time
    it has waited, and as a miss."""
    out, miss = [], 0
    for tr in due:
        if tr.first is not None and tr.first <= t_end:
            out.append(tr.first - tr.due)
        else:
            out.append(t_end - tr.due)
            miss += 1
    return out, miss


def inter_token_gaps(tracks: list, t0: float, t_end: float) -> list:
    """Every gap between two tokens of one request whose later token came
    back inside the window."""
    out = []
    for tr in tracks:
        ts = tr.times
        out.extend(ts[i] - ts[i - 1] for i in range(1, len(ts))
                   if t0 <= ts[i] <= t_end)
    return out


def tokens_in(tracks: list, t0: float, t_end: float) -> int:
    return sum(sum(1 for t in tr.times if t0 <= t <= t_end) for tr in tracks)


def stats_delta(before: list, after: list) -> dict:
    """Summed change of the numeric counters of several engines."""
    out = {}
    for b, a in zip(before, after):
        for k, v in a.items():
            if isinstance(v, (int, float)) and not isinstance(v, bool):
                out[k] = out.get(k, 0) + v - b.get(k, 0)
    return out


@dataclass
class Run:
    """What one run measured; per-layer readers take their numbers here."""
    tracks: list                 # every request served in the window
    due: list                    # those due in the window
    t0: float
    t_end: float
    dims: dict                   # the configuration's published sizes
    slots: int                   # decode slots over all engines
    prefill: dict                # PrefillEngine.stats change over the window
    decode: dict                 # DecodeEngine.stats change over the window
    peak: dict                   # the device's peaks (bench/peaks.py)
    trace: object = None         # trace.Reduced of a traced run

    @property
    def seconds(self) -> float:
        return self.t_end - self.t0

    def ttft_p95_s(self):
        return percentile(ttft(self.due, self.t_end)[0], 95)

    def ttft_p50_s(self):
        return percentile(ttft(self.due, self.t_end)[0], 50)

    def ttft_summary(self) -> dict:
        vals, miss = ttft(self.due, self.t_end)
        return {"p50": percentile(vals, 50), "p95": percentile(vals, 95),
                "max": max(vals, default=None), "misses": miss}

    def itl_p95_ms(self):
        p = percentile(inter_token_gaps(self.tracks, self.t0, self.t_end), 95)
        return None if p is None else p * 1e3

    def output_tok_s(self):
        return tokens_in(self.tracks, self.t0, self.t_end) / self.seconds


END_TO_END = {
    "ttft_p50_s": Run.ttft_p50_s,
    "itl_p95_ms": Run.itl_p95_ms,
    "output_tok_s": Run.output_tok_s,
}
