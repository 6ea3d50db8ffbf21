"""Host gap per decode step: the device-idle milliseconds of the traced
window inside the server's `omni.decode.round` spans (one decode engine's
table refresh, step dispatch, fetch and token bookkeeping), over the decode
steps of the window (DecodeEngine.stats `steps`; `bench/spantrace.py`)."""
from bench import spantrace


def read(run):
    st = spantrace.of(run)
    return None if st is None else value(st.spans, run.decode.get("steps", 0))


def value(spans: dict, steps: int):
    got = spans.get("omni.decode.round")
    return None if got is None or not steps else 1e3 * got[2] / steps
