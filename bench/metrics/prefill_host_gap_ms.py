"""Device-idle milliseconds per prefill round: the idle time of the traced
window inside the server's `omni.prefill.round` spans (one prefill engine's
round: resume, chunk dispatch, prefix snapshots, first-token sampling), over
the number of those spans in the window (`bench/spantrace.py`)."""
from bench import spantrace


def read(run):
    st = spantrace.of(run)
    return None if st is None else value(st.spans)


def value(spans: dict):
    got = spans.get("omni.prefill.round")
    return None if got is None else 1e3 * got[2] / got[1]
