"""The trace reduction (bench/trace.py) on a hand-written trace whose
answer is known, and on a short trace recorded on a TPU v5e."""
import pytest

from bench import trace
from bench.cells import ROOT

FIXTURE = ROOT / "bench" / "tests" / "fixtures" / "chat-prefix.xplane.pb.gz"


def _ev(mid, start_us, dur_us):
    return (f"events {{ metadata_id: {mid} offset_ps: {int(start_us * 1e6)} "
            f"duration_ps: {int(dur_us * 1e6)} }}")


def synthetic():
    """Window [0, 100) us; a step span [10, 40) and a wait span [50, 90);
    ops [12, 22), [20, 32) (overlapping) and [95, 115) (cut by the window's
    end); one call of the step program."""
    from jax.profiler import ProfileData
    meta = lambda i, n: f'event_metadata {{ key: {i} value {{ id: {i} ' \
                        f'name: "{n}" }} }}'
    return ProfileData.from_text_proto(f'''
planes {{ id: 1 name: "/host:CPU"
  lines {{ id: 1 name: "python" timestamp_ns: 1000
    {_ev(1, 0, 100)} {_ev(2, 10, 30)} {_ev(3, 50, 40)} }}
  {meta(1, "bench.window")} {meta(2, "bench.step")} {meta(3, "bench.wait")}
}}
planes {{ id: 2 name: "/device:TPU:0"
  lines {{ id: 1 name: "XLA Modules" timestamp_ns: 1000 {_ev(1, 12, 20)} }}
  lines {{ id: 2 name: "XLA Ops" timestamp_ns: 1000
    {_ev(2, 12, 10)} {_ev(3, 20, 12)} {_ev(2, 95, 20)} }}
  {meta(1, "jit__step_impl(42)")} {meta(2, "fusion.1")} {meta(3, "dot.2")}
}}''')


def test_reduction_of_a_known_trace():
    r = trace.reduce(synthetic())
    assert r.window_s == pytest.approx(100e-6)
    assert r.busy_s == pytest.approx(25e-6)          # [12, 32) + [95, 100)
    assert r.idle_share == pytest.approx(0.75)
    assert r.program(r"_step_impl") == (pytest.approx(20e-6), 1)
    assert r.program(r"_verify_impl") is None
    assert dict((k, pytest.approx(v)) for k, v in r.device_ops) == {
        "fusion.1": 15e-6, "dot.2": 12e-6}
    # [0, 12) lies in no span but the window; [32, 95) mostly in the wait
    assert dict(r.idle_gaps) == {"bench.wait": pytest.approx(63e-6),
                                 "other": pytest.approx(12e-6)}


def test_window_span_is_required():
    from jax.profiler import ProfileData
    with pytest.raises(ValueError, match="bench.window"):
        trace.reduce(ProfileData.from_text_proto(
            'planes { id: 2 name: "/device:TPU:0" }'))


def test_reduction_of_a_chip_trace():
    """Four seconds of qwen2-1.5b.chat-prefix traced on one TPU v5 lite
    (bench/run.py --trace 1): the prefill chunk and decode step programs
    by name, busy and idle that add up to the window, and every idle gap
    inside the server's step."""
    r = trace.reduce(trace.load(str(FIXTURE)))
    assert r.chips == 1
    assert 3.9 < r.window_s < 4.2
    assert 0 < r.busy_s < r.window_s
    prefill = r.program(r"^jit__resume_paged_impl$")
    decode = r.program(r"^jit__step_impl$")
    assert prefill[1] > 10 and decode[1] > 10
    assert prefill[0] + decode[0] <= r.busy_s
    assert len(r.device_ops) == 10
    secs = [s for _, s in r.device_ops]
    assert secs == sorted(secs, reverse=True)
    assert not any(" while" in n for n, _ in r.device_ops)
    assert sum(s for _, s in r.idle_gaps) == pytest.approx(
        r.window_s - r.busy_s, rel=1e-6)
    assert dict(r.idle_gaps).keys() <= {"bench.step", "bench.submit",
                                        "bench.wait", "other"}
