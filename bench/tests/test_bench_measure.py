"""End-to-end metric arithmetic (bench/measure.py): every request due in
the window counts toward time to first token, one with no first token by
the window's end at its wait so far."""
from types import SimpleNamespace

import pytest

from bench import measure


def track(due, times):
    return SimpleNamespace(due=due, times=times,
                           first=times[0] if times else None)


def test_ttft_counts_misses_at_their_wait():
    due = [track(0.0, [0.2, 0.3]), track(1.0, [1.5]), track(2.0, []),
           track(3.0, [12.0])]
    vals, miss = measure.ttft(due, t_end=10.0)
    assert vals == pytest.approx([0.2, 0.5, 8.0, 7.0])
    assert miss == 2
    run = measure.Run(tracks=due, due=due, t0=0.0, t_end=10.0, dims={},
                      slots=1, prefill={}, decode={}, peak={})
    assert run.ttft_p50_s() == pytest.approx(3.75)
    assert run.ttft_summary()["misses"] == 2
    assert run.itl_p95_ms() == pytest.approx(100.0)
    assert run.output_tok_s() == pytest.approx(0.3)     # 12.0 is late
