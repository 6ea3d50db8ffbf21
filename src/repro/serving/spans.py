"""Host spans of the serving loop, and the process-wide compile counter.

A span is a `jax.profiler.TraceAnnotation`. Under a profiler session it
lands in the same trace as the device's planes, on the same clock, so every
stretch in which the device sat idle can be put down to the innermost span
that was open on the host at that moment. Without a session a span only
checks that none is active, so the spans are always on.

The names are a fixed set (`SPANS`), each starting with `omni.`; a decode
step opens about five. Spans that work on one request carry its `rid`.

  omni.step             Server.step: the whole round
  omni.proxy.tick       Server._drain_actions: proxy tick, prefill dispatch,
                        decode admissions
  omni.decode.admit     DecodeEngine.admit_batch: KV handoff into slots
  omni.prefill.round    one PrefillEngine.step that has work
  omni.prefill.resume   prefix-store lookup and block mapping (`rid`)
  omni.prefill.chunk    dispatch of one chunk program (`rid`, `tokens`)
  omni.prefill.store    a prefix snapshot into the store (`rid`, `tokens`)
  omni.prefill.sample   first-token sampling and its one fetch (`n`)
  omni.decode.round     one decode engine's step and token bookkeeping
  omni.decode.dispatch  block-table refresh and enqueue of the step program
  omni.decode.fetch     the step's one device-to-host fetch
  omni.monitor          a placement or sparsity tick that drains counters

No span forces a device sync: the decode loop keeps one fetch per step.

`COMPILES` counts, for the whole process, the host time JAX spends tracing,
lowering and compiling programs. A load from the persistent compile cache
happens inside the compile phase and counts there, so JAX's own
cache-retrieval event is not added a second time. One `jax.monitoring`
listener feeds it; it runs only when JAX compiles, never per step.
"""
from __future__ import annotations

import threading
import time
from collections import deque

import jax

SPANS = frozenset({
    "omni.step", "omni.proxy.tick", "omni.decode.admit",
    "omni.prefill.round", "omni.prefill.resume", "omni.prefill.chunk",
    "omni.prefill.store", "omni.prefill.sample", "omni.decode.round",
    "omni.decode.dispatch", "omni.decode.fetch", "omni.monitor"})


def span(name: str, **args) -> jax.profiler.TraceAnnotation:
    """A host span named `name` (one of `SPANS`), with `args` attached."""
    if name not in SPANS:
        raise ValueError(f"unknown span {name!r}")
    return jax.profiler.TraceAnnotation(name, **args)


TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileCounter:
    """Host seconds of tracing, lowering and compiling, from JAX's own
    duration events, kept as a log of the last `LOG` phases (when each
    ended on `time.monotonic`'s clock, its seconds, its function name):

    - `seconds_between(t0, t1)`: seconds of the phases that ended inside
      [t0, t1] (cache loads included);
    - `by_fun_between(t0, t1)`: the same seconds per function name, the
      `TOP` largest.
    """
    PHASES = (TRACE_EVENT, LOWER_EVENT, COMPILE_EVENT)
    TOP = 20
    LOG = 8192

    def __init__(self):
        self._log: deque = deque(maxlen=self.LOG)   # (ended, seconds, fun)
        self._lock = threading.Lock()

    def on_event(self, event: str, secs: float, fun_name: str = None, **_):
        if event not in self.PHASES:
            return
        with self._lock:
            self._log.append((time.monotonic(), secs, str(fun_name)))

    def _between(self, t0: float, t1: float) -> list:
        with self._lock:
            return [(s, f) for t, s, f in self._log if t0 <= t <= t1]

    def seconds_between(self, t0: float, t1: float) -> float:
        return sum(s for s, _ in self._between(t0, t1))

    def by_fun_between(self, t0: float, t1: float) -> dict[str, float]:
        by_fun: dict[str, float] = {}
        for s, f in self._between(t0, t1):
            by_fun[f] = by_fun.get(f, 0.0) + s
        top = sorted(by_fun.items(), key=lambda kv: -kv[1])[:self.TOP]
        return dict(top)


COMPILES = CompileCounter()
jax.monitoring.register_event_duration_secs_listener(COMPILES.on_event)
