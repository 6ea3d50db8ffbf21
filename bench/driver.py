"""Open-loop drive of the server through its streaming front door.

Requests are submitted with `Server.add_request` when they fall due and
the window is advanced with `Server.step`; each token is stamped with the
host clock when the step that produced it returns. Every request is timed
from its due time, not from when the loop got round to submitting it, so a
stall shows as the queueing it imposes on later requests. The host spans
`bench.submit`, `bench.step` and `bench.wait` mark what the loop is doing
for the trace reduction.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax

from bench.traffic import Request

COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class Compiles:
    """Counts backend compilations (and their seconds) while it is on."""

    def __init__(self):
        self.n, self.seconds, self.on = 0, 0.0, False
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event: str, secs: float, **_):
        if self.on and event == COMPILE_EVENT:
            self.n += 1
            self.seconds += secs


@dataclass
class Track:
    req: Request
    due: float                       # host clock
    rid: Optional[int] = None
    submitted: Optional[float] = None
    times: list = field(default_factory=list)   # host clock of each token
    tokens: list = field(default_factory=list)
    reason: Optional[str] = None     # finish reason once finished

    @property
    def first(self) -> Optional[float]:
        return self.times[0] if self.times else None


def _params(max_tokens: int):
    from repro.core.proxy import SamplingParams
    return SamplingParams(max_tokens=int(max_tokens))   # greedy


class Loop:
    """Drives one server; keeps the track of every request it submitted."""

    def __init__(self, srv, clock=time.monotonic):
        self.srv = srv
        self.clock = clock
        self.by_rid: dict[int, Track] = {}

    def submit(self, tr: Track, now: float) -> None:
        from repro.core.proxy import BackpressureError
        with jax.profiler.TraceAnnotation("bench.submit"):
            try:
                tr.rid = self.srv.add_request(tr.req.prompt,
                                              _params(tr.req.max_tokens),
                                              now=tr.due)
            except BackpressureError:
                tr.reason = "shed"
                return
        tr.submitted = now
        self.by_rid[tr.rid] = tr

    def step(self) -> None:
        with jax.profiler.TraceAnnotation("bench.step"):
            outs = self.srv.step(self.clock())
        t = self.clock()
        for out in outs:
            tr = self.by_rid.get(out.rid)
            if tr is None:
                continue
            tr.tokens.extend(out.new_tokens)
            tr.times.extend([t] * len(out.new_tokens))
            if out.finished:
                tr.reason = out.finish_reason

    def busy(self) -> bool:
        return bool(self.srv.proxy.inflight)

    def until_idle(self, limit_s: float = 600.0) -> None:
        t0 = self.clock()
        while self.busy():
            if self.clock() - t0 > limit_s:
                raise RuntimeError("warm-up requests did not finish")
            self.step()

    def window(self, reqs: list, seconds: float,
               pending: Optional[list] = None) -> tuple[list, float, float]:
        """Serve `reqs` (offsets from the window's start) open-loop for
        `seconds`. → (tracks of the requests due, start, end)."""
        t0 = self.clock()
        t_end = t0 + seconds
        tracks = [Track(r, t0 + r.due_s) for r in reqs]
        k = 0
        while True:
            now = self.clock()
            if now >= t_end:
                break
            while k < len(tracks) and tracks[k].due <= now:
                self.submit(tracks[k], now)
                k += 1
            if self.busy():
                self.step()
                continue
            nxt = tracks[k].due if k < len(tracks) else t_end
            with jax.profiler.TraceAnnotation("bench.wait"):
                time.sleep(max(min(nxt, t_end) - self.clock(), 0.0))
        return tracks, t0, t_end


def warm(loop: Loop, mix, max_len: int, block: int, chunk: int,
         seed: int) -> None:
    """Compile every shape the window will use, before it opens.

    - first-token sampling and admission batches of 16, 8, 4, 2 and 1
      prompts finishing together;
    - each prefill chunk bucket (8, 16, 32, 64 tokens);
    - each decode block-table bucket up to the mix's longest context;
    - a prefix snapshot and a resume through it;
    - then the mix's shared prefixes, each stored in the server
      (`Mix.stored_prefixes`), so that the window opens on the prefix
      store of a server that has run this traffic.
    """
    import numpy as np
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 99])
    vocab = mix.vocab

    def prompt(n):
        return tuple(rng.integers(0, vocab, int(n)).tolist())

    def run(reqs):
        for r in reqs:
            loop.submit(Track(r, loop.clock()), loop.clock())
        loop.until_idle()

    for n in (16, 8, 4, 2, 1):
        run([Request(0.0, prompt(8), 2, -1, 0) for _ in range(n)])
    for r in (5, 12, 24, 40):
        run([Request(0.0, prompt(chunk + r), 2, -1, 0)])
    longest = min(mix.max_context(), max_len - 2)
    nb = 8
    while True:
        run([Request(0.0, prompt(min(nb * block, longest) - 1), 2, -1, 0)])
        if nb * block >= longest:
            break
        nb *= 2
    shared = prompt(3 * chunk // 2)
    run([Request(0.0, shared + prompt(20), 3, -1, 0)])
    run([Request(0.0, shared + prompt(30), 3, -1, 0)])
    run([Request(0.0, shared + prompt(40), 3, -1, 0)])
    run(mix.stored_prefixes(seed))
