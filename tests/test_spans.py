"""Host spans and the compile counter of the serving loop
(serving/spans.py): the documented span set, the counter against a fresh
jit, the prefill-start stamp of every request, arrivals stamped at their
scheduled time, and the spans a profiled step opens."""
import glob
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.core.proxy import OASConfig
from repro.serving import Server, ServerConfig
from repro.serving.spans import COMPILES, SPANS, CompileCounter, span


@pytest.fixture(scope="module")
def cfg():
    return reduced_config("qwen2-1.5b").with_updates(
        compute_dtype="float32", param_dtype="float32", n_layers=2)


def make_server(cfg, **kw):
    scfg = dict(n_prefill=1, n_decode=1, decode_slots=4, max_len=96,
                chunk_tokens=16, prefill_tick_budget=32,
                oas=OASConfig(defer_window=0.0))
    scfg.update(kw)
    return Server(cfg, ServerConfig(**scfg), pattern=[0, 0])


def prompts(cfg, seed=3):
    """Four prompts, two sharing a 24-token prefix."""
    rng = np.random.default_rng(seed)
    base = tuple(rng.integers(0, cfg.vocab_size, 24).tolist())
    return [base + tuple(rng.integers(0, cfg.vocab_size, 8).tolist()),
            base + tuple(rng.integers(0, cfg.vocab_size, 12).tolist()),
            tuple(rng.integers(0, cfg.vocab_size, 40).tolist()),
            tuple(rng.integers(0, cfg.vocab_size, 9).tolist())]


def serve(srv, reqs, max_tokens=4, limit_s=120.0):
    from repro.core.proxy import SamplingParams
    rids = [srv.add_request(p, SamplingParams(max_tokens=max_tokens))
            for p in reqs]
    t0 = time.monotonic()
    while srv.proxy.inflight and time.monotonic() - t0 < limit_s:
        srv.step()
    assert not srv.proxy.inflight
    return rids


def test_span_names_are_a_fixed_documented_set():
    assert SPANS and all(n.startswith("omni.") for n in SPANS)
    doc = __import__("repro.serving.spans", fromlist=["x"]).__doc__
    assert all(n in doc for n in SPANS)
    with span("omni.prefill.chunk", rid=3, tokens=16):
        pass
    with pytest.raises(ValueError, match="omni.nope"):
        span("omni.nope")


def test_compile_counter_counts_a_fresh_jit():
    from jax._src import monitoring
    listeners = monitoring.get_event_duration_listeners()
    assert listeners.count(COMPILES.on_event) == 1      # registered once
    x = jnp.ones(13)
    fresh = CompileCounter()
    jax.monitoring.register_event_duration_secs_listener(fresh.on_event)
    try:
        def _spans_probe(x):
            return jnp.tanh(x) * 7 + 1
        t0 = time.monotonic()
        jax.jit(_spans_probe)(x).block_until_ready()
        t1 = time.monotonic()
        jax.jit(_spans_probe)(x).block_until_ready()
        t2 = time.monotonic()
    finally:
        jax.monitoring.unregister_event_duration_listener(fresh.on_event)
    secs = fresh.seconds_between(t0, t1)
    assert secs > 0
    assert any("_spans_probe" in f for f in fresh.by_fun_between(t0, t1))
    assert COMPILES.seconds_between(t0, t1) >= secs * 0.999
    # the same program again compiles nothing
    assert fresh.seconds_between(t1, t2) == 0.0
    assert fresh.by_fun_between(t1, t2) == {}


def test_compile_counter_keeps_the_largest_functions():
    c = CompileCounter()
    compile_event = "/jax/core/compile/backend_compile_duration"
    for i in range(CompileCounter.TOP + 5):
        c.on_event(compile_event, 1.0 + i, fun_name=f"f{i}")
    # a cache load is already inside a compile's duration; other events
    # are not compiles
    c.on_event("/jax/compilation_cache/cache_retrieval_time_sec", 0.5)
    c.on_event("/jax/some/other_event", 9.0)
    time.sleep(1e-3)
    t_late = time.monotonic()
    c.on_event(compile_event, 0.25, fun_name="late")
    t_end = time.monotonic()
    every = c.by_fun_between(0.0, t_end)
    assert len(every) == CompileCounter.TOP
    assert "f0" not in every and f"f{CompileCounter.TOP + 4}" in every
    assert "late" not in every
    # a compile smaller than every kept one is still named in its window
    assert c.by_fun_between(t_late, t_end) == {"late": 0.25}
    assert c.seconds_between(0.0, t_end) == pytest.approx(
        sum(1.0 + i for i in range(CompileCounter.TOP + 5)) + 0.25)


def test_prefill_start_lies_between_arrival_and_first_token(cfg):
    srv = make_server(cfg)
    rids = serve(srv, prompts(cfg))
    done = {r.rid: r for r in srv.metrics.done}
    assert sorted(done) == sorted(rids)
    for r in done.values():
        assert r.arrival <= r.prefill_started <= r.first_token_time
        assert r.prefill_wait() == pytest.approx(
            r.prefill_started - r.arrival)
    s = srv.metrics.summary(1.0)
    assert 0.0 <= s["prefill_wait_mean"] <= s["ttft_mean"]
    for eng in srv.decodes:      # the spans add no device sync
        assert eng.stats["host_fetches"] == eng.stats["steps"] > 0


def test_run_stamps_arrivals_at_their_scheduled_time(cfg):
    """One slow step delays a later arrival's first token, and the delay
    counts in its TTFT: the arrival is its scheduled time, not the time
    the loop got round to it."""
    srv = make_server(cfg)
    serve(srv, prompts(cfg)[3:])                 # compile outside the run
    slow_s, offset = 0.4, 0.05
    step = srv.step
    calls = []

    def slow_first_step(now=None):
        calls.append(now)
        if len(calls) == 1:
            time.sleep(slow_s)
        return step(now)

    srv.step = slow_first_step
    n0 = len(srv.metrics.done)
    reqs = [(p, 3) for p in prompts(cfg, seed=5)[2:]]
    srv.run(reqs, max_wall_s=120, arrivals=[0.0, offset])
    early, late = sorted(srv.metrics.done[n0:], key=lambda r: r.rid)
    assert late.arrival - early.arrival == pytest.approx(offset, abs=1e-3)
    assert late.ttft() >= slow_s - offset


def test_profiled_steps_open_the_documented_spans(cfg, tmp_path):
    from jax.profiler import ProfileData
    srv = make_server(cfg)
    serve(srv, prompts(cfg, seed=7)[3:])         # compile outside the trace
    steps0 = srv.decodes[0].stats["steps"]
    jax.profiler.start_trace(str(tmp_path))
    try:
        rids = serve(srv, prompts(cfg, seed=8))
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    events = []
    for plane in ProfileData.from_file(path[0]).planes:
        for line in plane.lines:
            events += [(e.start_ns, e.end_ns, e.name, dict(e.stats))
                       for e in line.events if e.name.startswith("omni.")]
    names = {e[2] for e in events}
    assert names <= SPANS
    assert names >= SPANS - {"omni.monitor"}
    for _, _, name, args in events:
        if name in ("omni.prefill.chunk", "omni.prefill.store"):
            assert args["rid"] in rids and args["tokens"] > 0
    # every request is first picked inside a prefill round, where a resume
    # or a chunk span carries its rid: the first one splits its time to
    # first token into wait and prefill work
    rounds = [(a, b) for a, b, n, _ in events if n == "omni.prefill.round"]
    picked = {args["rid"] for a, _, n, args in events
              if n in ("omni.prefill.resume", "omni.prefill.chunk")
              and any(r0 <= a <= r1 for r0, r1 in rounds)}
    assert picked == set(rids)
    # a resume span opens only where a stored prefix is looked up: once
    # when a request is queued, and once more if it has not started when
    # picked
    resumes = [args["rid"] for _, _, n, args in events
               if n == "omni.prefill.resume"]
    assert all(1 <= resumes.count(r) <= 2 for r in rids)
    steps = [e for e in events if e[2] == "omni.step"]
    fetches = [e for e in events if e[2] == "omni.decode.fetch"]
    assert len(fetches) == srv.decodes[0].stats["steps"] - steps0
    assert all(any(s[0] <= f[0] and f[1] <= s[1] for s in steps)
               for f in fetches)
