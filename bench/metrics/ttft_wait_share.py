"""Share of time to first token spent before any prefill work began, in %:
over the requests due in the window whose first token came back in it,
the sum of (first pick by a prefill round - due) over the sum of (first
token - due). The first pick is the start of the first span carrying the
request's `rid` inside an `omni.prefill.round` of the traced window, a
resume or a chunk (`bench/spantrace.py`), on the run's clock through the
window's start."""
from bench import spantrace


def read(run):
    st = spantrace.of(run)
    return None if st is None else value(run, st.picked)


def value(run, picked: dict):
    """`picked`: request id -> seconds from the window's start."""
    wait = ttft = 0.0
    for tr in run.due:
        if tr.first is None or tr.first > run.t_end or tr.rid not in picked:
            continue
        wait += run.t0 + picked[tr.rid] - tr.due
        ttft += tr.first - tr.due
    return 100.0 * wait / ttft if ttft > 0 else None
