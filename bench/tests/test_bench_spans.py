"""The reduction of the server's own spans (bench/spantrace.py) on a
hand-written trace whose answer is known and on a trace recorded on a TPU
v5e, and the four per-layer metrics that read the spans and the program's
compile counter, on hand-made run records."""
import shutil
import sys
import time
from types import SimpleNamespace

import pytest

from bench import cells, measure, spantrace, trace
from bench.cells import ROOT

FIXTURE = ROOT / "bench" / "tests" / "fixtures" / "chat-prefix.xplane.pb.gz"


def reader(name):
    return cells.load_module(ROOT / "bench" / "metrics" / f"{name}.py",
                             "test_metric_" + name)


def _ev(mid, start_us, dur_us, rid=None):
    stat = "" if rid is None else \
        f"stats {{ metadata_id: 1 int64_value: {rid} }}"
    return (f"events {{ metadata_id: {mid} offset_ps: {int(start_us * 1e6)} "
            f"duration_ps: {int(dur_us * 1e6)} {stat} }}")


HOST = [  # (name, start us, duration us, rid)
    ("bench.window", 0, 100, None),
    ("omni.prefill.resume", 3, 1, 9),        # in the proxy tick: no pick
    ("omni.prefill.resume", 4, 1, 7),        # in the proxy tick: no pick
    ("bench.step", 10, 50, None),
    ("omni.step", 11, 47, None),
    ("omni.prefill.round", 12, 18, None),
    ("omni.prefill.resume", 12, 1, 5),
    ("omni.prefill.chunk", 13, 7, 5),
    ("omni.prefill.chunk", 21, 2, 7),        # rid 7's first touch
    ("omni.decode.round", 31, 26, None),
    ("omni.decode.dispatch", 31, 2, None),
    ("omni.decode.fetch", 33, 17, None),
    ("bench.wait", 62, 33, None),
]
OPS = [(14, 8), (34, 6), (45, 3), (96, 20)]


def synthetic():
    """Window [0, 100) us. Device ops [14, 22), [34, 40), [45, 48) and
    [96, 110); idle [0, 14) outside every span, [22, 34) inside the prefill
    round, [40, 45) inside the decode fetch, [48, 96) mostly in the wait."""
    from jax.profiler import ProfileData
    meta = lambda i, n: f'event_metadata {{ key: {i} value {{ id: {i} ' \
                        f'name: "{n}" }} }}'
    names = sorted({n for n, *_ in HOST})
    ids = {n: i + 1 for i, n in enumerate(names)}
    host = " ".join(_ev(ids[n], a, d, rid) for n, a, d, rid in HOST)
    ops = " ".join(_ev(1, a, d) for a, d in OPS)
    return ProfileData.from_text_proto(f'''
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000 {host} }}
  {" ".join(meta(ids[n], n) for n in names)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "rid" }} }}
}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000 {_ev(2, 14, 8)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000 {ops} }}
  {meta(1, "fusion.1")} {meta(2, "jit__resume_paged_impl(3)")}
}}''')


def us(d: dict) -> dict:
    return {k: pytest.approx(v * 1e-6) for k, v in d.items()}


def test_gaps_are_named_by_the_innermost_program_span():
    st = spantrace.reduce(synthetic())
    assert st.window_s == pytest.approx(100e-6)
    assert dict(st.idle_gaps) == us({
        "other": 14, "omni.prefill.round": 12, "omni.decode.fetch": 5,
        "bench.wait": 48})
    # the bench.* reduction sees the same busy time, with both gaps inside
    # the server's step put down to the step as a whole
    red = trace.reduce(synthetic())
    assert red.busy_s == pytest.approx(21e-6)
    assert dict(red.idle_gaps) == us({"other": 14, "bench.step": 17,
                                      "bench.wait": 48})


def test_span_seconds_counts_and_idle():
    st = spantrace.reduce(synthetic())
    got = {k: (pytest.approx(v[0]), v[1], pytest.approx(v[2]))
           for k, v in st.spans.items()}
    want = {  # seconds, count, idle (children included), us
        "omni.step": (47, 1, 30),
        "omni.prefill.round": (18, 1, 10),
        "omni.prefill.resume": (3, 3, 3),
        "omni.prefill.chunk": (9, 2, 2),
        "omni.decode.round": (26, 1, 17),
        "omni.decode.dispatch": (2, 1, 2),
        "omni.decode.fetch": (17, 1, 8),
    }
    assert got == {k: (s * 1e-6, n, i * 1e-6)
                   for k, (s, n, i) in want.items()}
    # rid 5 was picked by a prefill round 12 us into the window (its
    # resume), rid 7 at 21 us (its first chunk: it resumed when queued);
    # rid 9's resume ran in the proxy tick, outside every round
    assert st.picked == {5: pytest.approx(12e-6), 7: pytest.approx(21e-6)}


def test_chip_trace_without_program_spans():
    """The recorded chip trace predates the server's spans: its bench.*
    reduction is unchanged, and the program-span reduction names the same
    gaps and finds no span and no pick."""
    prof = trace.load(str(FIXTURE))
    r = trace.reduce(prof)
    assert r.busy_s == pytest.approx(3.620878625, rel=1e-9)
    assert r.window_s == pytest.approx(4.039095233, rel=1e-9)
    assert r.idle_share == pytest.approx(0.10354215087158858, rel=1e-9)
    assert r.programs["jit__resume_paged_impl"] == [
        pytest.approx(2.5447205169999996, rel=1e-9), 59]
    assert r.programs["jit__step_impl"] == [
        pytest.approx(1.0751620299999998, rel=1e-9), 22]
    assert len(r.programs) == 7
    assert r.device_ops[0] == [
        "bitcast_dynamic-update-slice_fusion.14 = bf16[28,9281,2,16,128] "
        "fusion", pytest.approx(0.5265732410000001, rel=1e-9)]
    st = spantrace.reduce(prof)
    assert st.idle_gaps == [["bench.step", pytest.approx(r.idle_gaps[0][1])]]
    assert r.idle_gaps == [["bench.step",
                            pytest.approx(0.41821660800000926, rel=1e-9)]]
    assert st.spans == {} and st.picked == {}


def test_of_reads_the_runs_own_profile(tmp_path):
    shutil.copy(FIXTURE, tmp_path / "run.xplane.pb.gz")
    red = trace.reduce(trace.load(str(FIXTURE)))
    run = SimpleNamespace(trace=red)
    st = spantrace.of(run, tmp_path)
    assert st is not None and st.window_s == pytest.approx(red.window_s)
    assert spantrace.of(SimpleNamespace(trace=None), tmp_path) is None
    other = SimpleNamespace(trace=SimpleNamespace(window_s=30.0))
    assert spantrace.of(other, tmp_path) is None
    assert spantrace.of(run, tmp_path / "empty") is None


def track(rid, due, first):
    return SimpleNamespace(rid=rid, due=due, times=[] if first is None
                           else [first], first=first)


def test_ttft_wait_share_by_hand():
    t0 = 100.0
    due = [track(1, t0 + 1.0, t0 + 1.5),     # waited 0.2 of 0.5
           track(2, t0 + 2.0, t0 + 2.4),     # waited 0.1 of 0.4
           track(3, t0 + 3.0, None),         # no first token: left out
           track(4, t0 + 4.0, t0 + 40.0),    # first token after the end
           track(5, t0 + 5.0, t0 + 5.5)]     # never picked in the trace
    run = measure.Run(tracks=due, due=due, t0=t0, t_end=t0 + 30.0, dims={},
                      slots=1, prefill={}, decode={}, peak={})
    m = reader("ttft_wait_share")
    picked = {1: 1.2, 2: 2.1, 4: 4.5}
    assert m.value(run, picked) == pytest.approx(100 * 0.3 / 0.9)
    assert m.value(run, {}) is None
    assert m.read(run) is None                # untraced


def test_host_gap_readers_by_hand():
    pre, dec = reader("prefill_host_gap_ms"), reader("decode_host_gap_ms")
    assert pre.value({"omni.prefill.round": [1.0, 4, 0.2]}) == \
        pytest.approx(50.0)
    assert pre.value({}) is None
    spans = {"omni.decode.round": [3.0, 100, 0.25]}
    assert dec.value(spans, 100) == pytest.approx(2.5)
    assert dec.value(spans, 0) is None
    assert dec.value({}, 100) is None
    run = SimpleNamespace(trace=None, decode={"steps": 5})
    assert pre.read(run) is None and dec.read(run) is None
    st = spantrace.reduce(synthetic())
    assert pre.value(st.spans) == pytest.approx(10e-3)
    assert dec.value(st.spans, 1) == pytest.approx(17e-3)


def test_compile_s_in_window_reads_the_program_counter(monkeypatch):
    import jax
    import jax.numpy as jnp
    import repro.serving.spans  # noqa: F401  (the counter listens from here)
    m = reader("compile_s_in_window")
    t0 = time.monotonic()
    jax.jit(lambda x: x * 5 - 2)(jnp.ones(11)).block_until_ready()
    run = SimpleNamespace(t0=t0, t_end=time.monotonic())
    assert m.read(run) > 0
    assert m.read(SimpleNamespace(t0=-2.0, t_end=-1.0)) == 0.0   # none
    # a program without the counter gives nothing, and raises nothing
    monkeypatch.setitem(sys.modules, "repro.serving.spans", None)
    assert m.read(run) is None
