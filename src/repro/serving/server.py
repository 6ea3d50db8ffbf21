"""PD-disaggregated continuous-batching server: OmniProxy + engines, wall-clock.

The end-to-end driver for deliverable (b): serves a real (small) model with
batched requests through the full paper stack — APC-aware prefill dispatch
with radix-backed partial-prefix KV reuse, chunked prefill interleaved with
decode rounds (the prefill_tick_budget knob arbitrates the TTFT/TPOT
trade-off per tick), LPT decode scheduling with batched admission, deferred
submission, sink+recent compressed caches, and (for MoE configs)
OmniPlacement live expert-load monitoring with pipelined weight migration.

Request-level API (vLLM-style): `add_request(prompt, SamplingParams) → rid`
registers an open-loop request with its own temperature/top-k/top-p/seed/
stop-token configuration; `step()` advances every engine one round and
returns per-request `RequestOutput` deltas (new tokens + finish_reason in
{stop, length, abort}); `abort(rid)` cancels a request wherever it lives
(proxy pools, prefill queues, pending KV handoffs, decode slots + KVPool
blocks); `generate(prompts, params)` is a streaming iterator over the same
primitives. `run()` — the closed-batch entry the benchmarks use — is a thin
loop over add_request/step, so greedy outputs are unchanged.

Request lifecycle: proxy tick (eq. 8 dispatch) → chunked prefill (shortest-
remaining-first across queued prompts, resumed at radix prefix boundaries) →
KV handoff (batched donated insert) → continuous-batch decode (device-side
slot state incl. per-slot sampling params + PRNG keys; KVPool-preempted
requests re-enter decode_wait with their extracted cache). See
docs/serving.md.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core.placement import DynamicScheduler, SchedulerConfig
from repro.core.placement.migration import tables_from_placement_from_slots
from repro.core.proxy import (BackpressureError, MetricsAggregator, OASConfig,
                              OmniProxy, Phase, Request, RequestOutput,
                              SamplingParams)
from repro.models import moe as moe_mod
from repro.models.lm import LM
from repro.serving.arena import BlockHandoff, KVArena
from repro.serving.decode import DecodeEngine
from repro.serving.placement import DevicePlacement
from repro.serving.prefill import PrefillEngine
from repro.serving.quant import QuantConfig, QuantController
from repro.serving.spans import span
from repro.serving.spec import SpecConfig


@dataclass
class ServerConfig:
    n_prefill: int = 1
    n_decode: int = 1
    decode_slots: int = 8
    max_len: int = 256
    oas: OASConfig = field(default_factory=OASConfig)
    chunked_prefill: bool = True      # chunk + interleave prefill with decode
    chunk_tokens: int = 64            # prefill chunk size (jit bucket ceiling)
    prefill_tick_budget: int = 128    # prefill tokens per tick: ↑TTFT-biased,
                                      # ↓TPOT-biased (the paper's P/D knob)
    prefix_reuse: bool = True         # radix partial-prefix KV resume
    prefix_cache_cap: int = 32        # stored prefixes per prefill instance
    prefix_cache_cap_bytes: Optional[int] = None   # byte cap (real sizes)
    kv_blocks: Optional[int] = None   # decode KVPool size override
    paged_kv: bool = True             # physically paged decode KV arenas
    kv_block_size: int = 16           # tokens per KV block
    enable_placement: bool = True     # OmniPlacement dynamic scheduler
    placement_interval: int = 16      # decode steps between monitor ticks
    eos_token: int = -1               # DEPRECATED: server-global stop token,
                                      # used only when a request's
                                      # SamplingParams.stop_token_ids is
                                      # empty (-1 → run to max_tokens)
    idle_sleep_s: float = 0.01        # max per-iteration sleep while run()
                                      # waits for a future arrival
    spec: Optional[SpecConfig] = None  # model-free speculative decoding
                                       # (SpecPlane; None → off, no change)
    quant: Optional[QuantConfig] = None  # int8 paged KV arenas (QuantPlane;
                                         # None → off, f32 arenas unchanged)
    # ---- FaultPlane recovery knobs (None → off, no behavior change) ----
    watchdog_steps: Optional[int] = None    # retire a request whose progress
                                            # marker is unchanged for N steps
                                            # with finish_reason="timeout"
    watchdog_wall_s: Optional[float] = None  # same, wall-clock bound
    admission_queue_cap: Optional[int] = None  # shed (BackpressureError) when
                                               # the admission backlog exceeds
                                               # this many waiting requests
    placement_cfg: Optional[SchedulerConfig] = None  # OmniPlacement scheduler
                                               # override (None → defaults
                                               # with budget=0, table-width
                                               # max_slots)


class Server:
    def __init__(self, cfg: ModelConfig, scfg: ServerConfig,
                 mesh=None, rng=None,
                 pattern: Optional[list] = None, params=None, faults=None,
                 placement: Optional[DevicePlacement] = None):
        self.cfg, self.scfg = cfg, scfg
        # FaultPlane (serving/faults.py): seeded deterministic fault
        # injection, fired at the top of every step() before any engine work
        self.faults = faults
        # every engine is constructed through the explicit device-placement
        # layer; `mesh` (a MeshCtx) survives as the back-compat spelling
        self.placement = DevicePlacement.of(
            placement if placement is not None else mesh)
        self.mesh = self.placement.ctx
        self.lm = LM.build(cfg, self.mesh, pattern=pattern)
        self.params = self.placement.place_params(self.lm, params) \
            if params is not None else \
            self.lm.init(rng if rng is not None else jax.random.PRNGKey(0))
        self.tables = self.placement.replicate(self.lm.default_tables())
        self.proxy = OmniProxy(scfg.n_prefill, scfg.n_decode, scfg.oas)
        self.metrics = MetricsAggregator()
        # one shared paged-KV runtime for every co-located engine: prefill
        # writes chunk KV straight into its arenas, decode extends them, and
        # admission hands over block tables — zero-copy. The default pool
        # grants every decode slot max_len capacity plus one prompt of
        # prefill headroom per prefill instance; prefix-store snapshots
        # share the pool and are reclaimed (LRU) under pressure.
        self.kv_arena = None
        # QuantPlane: validate the knobs against this stack (raises on
        # quant-over-dense-KV; degrades to None when no full-attention
        # layer exists to quantize) BEFORE any arena is allocated
        self.quant_ctl = QuantController.from_model(
            cfg, self.lm.plan, scfg.quant, scfg.kv_block_size,
            paged_kv=scfg.paged_kv)
        if scfg.paged_kv:
            max_blocks = -(-scfg.max_len // scfg.kv_block_size)
            n_blocks = scfg.kv_blocks if scfg.kv_blocks is not None else \
                (scfg.n_decode * scfg.decode_slots + scfg.n_prefill) \
                * max_blocks
            self.kv_arena = KVArena.build(self.lm, n_blocks,
                                          scfg.kv_block_size,
                                          placement=self.placement,
                                          quant=self.quant_ctl is not None)
        self.prefills = [
            PrefillEngine(self.lm, self.params, self.tables, scfg.max_len,
                          chunk_tokens=scfg.chunk_tokens,
                          enable_chunked=scfg.chunked_prefill,
                          allow_partial_reuse=scfg.prefix_reuse,
                          cache_cap=scfg.prefix_cache_cap,
                          cache_cap_bytes=scfg.prefix_cache_cap_bytes,
                          tree=self.proxy.trees[i],
                          arena=self.kv_arena,
                          placement=self.placement)
            for i in range(scfg.n_prefill)]
        self.decodes = [DecodeEngine(self.lm, self.params, self.tables,
                                     scfg.decode_slots, scfg.max_len,
                                     kv_blocks=scfg.kv_blocks,
                                     paged=scfg.paged_kv,
                                     block_size=scfg.kv_block_size,
                                     arena=self.kv_arena,
                                     placement=self.placement,
                                     spec=scfg.spec,
                                     spec_radix=self.proxy.trees[0]
                                     if self.proxy.trees else None)
                        for _ in range(scfg.n_decode)]
        if self.quant_ctl is not None:
            # static residency figures next to the per-step counters — the
            # benches read these from decode_stats like every other plane
            for eng in self.decodes:
                eng.stats.update(QuantController.stats_keys())
                self.quant_ctl.note(eng.stats)
        # rid → (cache B=1, next_token, pos, cached_tokens, prompt, params)
        # awaiting admission (prompt drives prefix-block sharing in the
        # paged pool; params land in the slot's device-side sampling state)
        self._pending_kv: dict[int, tuple] = {}
        self._step_count = 0
        self.n_migrations = 0
        # streaming-output plumbing: per-step token deltas, finish records,
        # and out-of-band events (aborts), flushed by step()
        self._next_rid = 0
        self._fresh: dict[int, list[int]] = {}
        self._emitted: dict[int, int] = {}          # rid → tokens delivered
        self._finish_info: dict[int, tuple] = {}    # rid → (reason, total)
        self._events: list[RequestOutput] = []
        self._idle_slept_s = 0.0
        # watchdog state: rid → (progress marker, step seen, wall seen)
        self._wd: dict[int, tuple] = {}
        self.n_handoffs_swept = 0
        self.placement_sched = None
        if scfg.enable_placement and cfg.moe.n_experts:
            s = int(self.tables["slot_expert"].shape[1])
            placement = moe_mod.round_robin_placement(cfg.moe.n_experts,
                                                      self.mesh.ep, s)
            # the engine applies ONE placement table across layers, so the
            # monitor runs on layer-summed counts (n_layers=1 collapse)
            pcfg = scfg.placement_cfg
            if pcfg is None:
                pcfg = SchedulerConfig(budget=0, max_slots=s)
            self.placement_sched = DynamicScheduler(
                ep=self.mesh.ep, n_experts=cfg.moe.n_experts, n_layers=1,
                cfg=pcfg, placements=[placement])
        self.migration_log: list[dict] = []
        self._remap_stack = None        # lazily-built donated remap jit

    # ---- request-level API -------------------------------------------
    def add_request(self, prompt: tuple,
                    params: Optional[SamplingParams] = None,
                    now: Optional[float] = None) -> int:
        """Register an open-loop request under its own SamplingParams;
        → rid. Tokens stream back through step() / generate()."""
        now = time.monotonic() if now is None else now
        params = params if params is not None else SamplingParams()
        rid = self._next_rid
        while rid in self.proxy.inflight:       # never collide with a live
            rid += 1                            # caller-chosen submit() rid
        return self._submit(rid, tuple(prompt), params, now)

    def submit(self, rid: int, prompt: tuple, max_tokens: int, now: float):
        """Legacy closed-batch entry: caller-chosen rid, greedy decoding,
        server-global eos_token. Prefer add_request()."""
        self._submit(rid, tuple(prompt),
                     SamplingParams(max_tokens=max_tokens), now)

    def _submit(self, rid: int, prompt: tuple, params: SamplingParams,
                now: float) -> int:
        self._admission_check(prompt)
        self.proxy.submit(Request(rid, prompt, params.max_tokens,
                                  arrival=now, sampling=params), now)
        self._next_rid = max(self._next_rid, rid + 1)
        return rid

    def _admission_check(self, prompt: tuple):
        """Graceful load shedding: reject at the door — with a typed
        BackpressureError the caller can act on — instead of admitting a
        request that would defer inside the engines forever (livelock).
        Two gates: a prompt no sequence of releases could ever make fit
        (larger than every non-quarantined block), and a bounded admission
        backlog (`admission_queue_cap`, None → unbounded)."""
        if self.kv_arena is not None:
            pool = self.kv_arena.pool
            usable = pool.n_blocks - len(pool.quarantined)
            need = pool.blocks_for(len(prompt))
            if need > usable:
                self.metrics.note_shed()
                raise BackpressureError(
                    f"prompt needs {need} KV blocks but the pool has only "
                    f"{usable} usable ({len(pool.quarantined)} quarantined)")
        cap = self.scfg.admission_queue_cap
        if cap is not None:
            backlog = (len(self.proxy.pending) + len(self.proxy.decode_wait)
                       + len(self._pending_kv)
                       + sum(len(e.queue) for e in self.prefills))
            if backlog >= cap:
                self.metrics.note_shed()
                raise BackpressureError(
                    f"admission backlog {backlog} >= cap {cap}")

    def step(self, now: Optional[float] = None) -> list[RequestOutput]:
        """Advance the whole server one round (proxy tick → prefill round →
        decode round) and return per-request deltas: every token generated
        this step, plus finish records (finish_reason in {stop, length})
        and abort notifications."""
        now = time.monotonic() if now is None else now
        with span("omni.step"):
            if self.faults is not None:
                # fire scheduled faults (and run their recovery) BEFORE
                # this step's engine rounds: no token is ever computed from
                # corrupt or lost KV, which is what makes completed outputs
                # bit-identical to the fault-free run
                self.faults.on_step(self, self._step_count, now)
            if self.kv_arena is not None:
                self._sweep_orphan_handoffs()
            with span("omni.proxy.tick"):
                self._drain_actions(now)
            self._sweep_failed(now)
            self._prefill_round()
            self._decode_round()
            self._watchdog(now)
            return self._flush_outputs()

    def abort(self, rid: int, now: Optional[float] = None) -> bool:
        """Cancel a request wherever it lives: proxy pools, prefill queues,
        pending KV handoffs, decode slots + KVPool blocks. → True if the
        rid was in flight. The next step() (or this call's generate()
        consumer) sees a RequestOutput(finished, finish_reason="abort")."""
        now = time.monotonic() if now is None else now
        req = self.proxy.abort(rid, now)
        if req is None:
            return False
        kv = self._pending_kv.pop(rid, None)
        if kv is not None:
            self._release_handoff(kv[0])
        for eng in self.prefills:
            eng.abort(rid)
        for eng in self.decodes:
            eng.release(rid)                    # no-op where not resident
        self._fresh.pop(rid, None)
        self._finish_info.pop(rid, None)
        n_out = max(len(req.output_tokens), self._emitted.pop(rid, 0))
        self.metrics.add_aborted(req)
        self._events.append(RequestOutput(rid, (), True, "abort", n_out))
        return True

    def generate(self, prompts, params=None,
                 max_wall_s: float = 300.0) -> Iterator[RequestOutput]:
        """Streaming front door: submit one prompt (tuple of ints) or a
        list of prompts — `params` a single SamplingParams, a matching
        list, or None (greedy) — then drive step() and yield every
        RequestOutput as it materializes until all submitted requests
        finish. Yields include any other in-flight requests' outputs (the
        caller drives the shared engine loop)."""
        single = bool(prompts) and isinstance(prompts[0], (int, np.integer))
        plist = [tuple(prompts)] if single else [tuple(p) for p in prompts]
        if params is None or isinstance(params, SamplingParams):
            pparams = [params] * len(plist)
        else:
            pparams = list(params)
            if len(pparams) != len(plist):
                raise ValueError(f"{len(plist)} prompts but "
                                 f"{len(pparams)} SamplingParams")
        t0 = time.monotonic()
        live = {self.add_request(p, sp, now=t0)
                for p, sp in zip(plist, pparams)}
        while live and time.monotonic() - t0 < max_wall_s:
            for out in self.step():
                if out.finished:
                    live.discard(out.rid)
                yield out

    # ---- internals ---------------------------------------------------
    def _release_handoff(self, cache) -> None:
        """Free the arena blocks a zero-copy handoff still owns. Every
        request exit path that drops a cache-bearing record before decode
        admission (abort, early finish, stale re-dispatch result, drained
        _pending_kv) MUST route through here — a missed release leaks
        shared-arena blocks permanently."""
        if isinstance(cache, BlockHandoff):
            self.kv_arena.pool.release(cache.key)

    # ---- FaultPlane recovery machinery -------------------------------
    def _retire_faulted(self, rid: int, reason: str, now: float):
        """Retire a request the recovery machinery gave up on (`"error"`:
        retries exhausted, `"timeout"`: watchdog): release every engine/pool
        resource it holds and emit a terminal RequestOutput. Reuses
        proxy.abort for the accounting unwind — a Phase.FAILED request
        matches no accounting branch by construction."""
        req = self.proxy.abort(rid, now)
        if req is None:
            return
        req.finish_reason = reason
        kv = self._pending_kv.pop(rid, None)
        if kv is not None:
            self._release_handoff(kv[0])
        for eng in self.prefills:
            eng.abort(rid)
        for eng in self.decodes:
            eng.release(rid)
        self._fresh.pop(rid, None)
        self._finish_info.pop(rid, None)
        self._wd.pop(rid, None)
        n_out = max(len(req.output_tokens), self._emitted.pop(rid, 0))
        if reason == "timeout":
            self.metrics.add_timeout(req)
        else:
            self.metrics.add_error(req)
        self._events.append(RequestOutput(rid, (), True, reason, n_out))

    def _sweep_failed(self, now: float):
        """Retire every Phase.FAILED request with finish_reason="error".
        Retry-cap exhaustion (and the no-healthy-instance tick path) only
        advances the phase — without this sweep a FAILED request would sit
        in proxy.inflight forever and run()/generate() would never return
        (the pre-FaultPlane livelock)."""
        for rid in [r.rid for r in list(self.proxy.inflight.values())
                    if r.phase == Phase.FAILED]:
            self._retire_faulted(rid, "error", now)

    def _watchdog(self, now: float):
        """Retire requests whose progress marker has not changed for
        `watchdog_steps` server steps or `watchdog_wall_s` seconds with
        finish_reason="timeout". The marker collapses DECODE_WAIT and
        DECODE_SCHEDULED into one class — admission-requeue ping-pong is
        not progress and must not reset the timer — while prefill cursor
        advance, new output tokens, and a granted retry each re-earn the
        full window."""
        ws, ww = self.scfg.watchdog_steps, self.scfg.watchdog_wall_s
        if ws is None and ww is None:
            return
        live = set()
        for rid, req in list(self.proxy.inflight.items()):
            live.add(rid)
            phase_class = (Phase.DECODE_WAIT if req.phase in
                           (Phase.DECODE_WAIT, Phase.DECODE_SCHEDULED)
                           else req.phase)
            cursor = 0
            for eng in self.prefills:
                for t in eng.queue:
                    if t.rid == rid:
                        cursor = max(cursor, t.cursor)
            marker = (phase_class, cursor, len(req.output_tokens),
                      req.n_retries)
            prev = self._wd.get(rid)
            if prev is None or prev[0] != marker:
                self._wd[rid] = (marker, self._step_count, now)
                continue
            _, step0, t0 = prev
            if (ws is not None and self._step_count - step0 >= ws) or \
                    (ww is not None and now - t0 >= ww):
                self._retire_faulted(rid, "timeout", now)
                live.discard(rid)
        for rid in [r for r in self._wd if r not in live]:
            del self._wd[rid]

    def _sweep_orphan_handoffs(self):
        """Leak backstop for the `("handoff", i)` rename stage: a handoff
        key in the pool referenced by neither a parked `_pending_kv` record
        nor an engine's undelivered-result cache belongs to nobody — no
        code path will ever admit or release it. Dead-instance drops and
        injected handoff faults land here; released blocks return to the
        free list and the sweep is counted (`n_handoffs_swept`)."""
        pool = self.kv_arena.pool
        refs = {kv[0].key for kv in self._pending_kv.values()
                if isinstance(kv[0], BlockHandoff)}
        for eng in self.prefills:
            for r in eng._ready:
                if isinstance(r.cache, BlockHandoff):
                    refs.add(r.cache.key)
        for key in list(pool.per_request):
            if isinstance(key, tuple) and len(key) == 2 \
                    and key[0] == "handoff" and key not in refs:
                pool.release(key)
                self.n_handoffs_swept += 1

    def recover_corruption(self, now: Optional[float] = None) -> list:
        """Summary-plane corruption recovery: scan the arena for blocks
        whose stored key summaries disagree with their content, then (1)
        drop prefix-store entries built on them, (2) drop parked handoffs
        and (3) in-flight prefill work touching them (rerouting those
        requests retry-capped), (4) restart resident decode requests mapping
        them, and (5) quarantine + scrub the now-unmapped blocks so they
        leave circulation with a coherent (all-zero) summary. → condemned
        block ids. Restarted requests regenerate bit-identical prefixes
        (positional draws) and the delivered counter suppresses re-streaming."""
        if self.kv_arena is None:
            return []
        now = time.monotonic() if now is None else now
        bad = self.kv_arena.find_corrupt_blocks()
        if not bad:
            return []
        badset = set(bad)
        pool = self.kv_arena.pool
        # an already-orphaned handoff key may map a condemned block — sweep
        # first so the holder scan below sees only live owners
        self._sweep_orphan_handoffs()
        for eng in self.prefills:
            eng.store.drop_containing(badset)
        for rid in list(self._pending_kv):
            kv = self._pending_kv[rid]
            if isinstance(kv[0], BlockHandoff) and badset & set(kv[0].blocks):
                self._pending_kv.pop(rid)
                self._release_handoff(kv[0])
                req = self.proxy.inflight.get(rid)
                if req is not None:
                    self.proxy.on_handoff_lost(req, now)
        for eng in self.prefills:
            hit = {r.rid for r in eng._ready
                   if isinstance(r.cache, BlockHandoff)
                   and badset & set(r.cache.blocks)}
            hit |= {t.rid for t in eng.queue
                    if badset & set(pool.owned(("prefill", t.rid)))}
            for rid in hit:
                eng.abort(rid)
                req = self.proxy.inflight.get(rid)
                if req is not None:
                    self.proxy.on_prefill_restart(req, now)
        for eng in self.decodes:
            for rid in list(eng.rid_slot):
                if badset & set(pool.owned(rid)):
                    eng.release(rid)
                    req = self.proxy.inflight.get(rid)
                    if req is not None and req.phase == Phase.DECODE_RUNNING:
                        self.proxy.on_decode_restart(req, now)
        self._sweep_failed(now)
        for b in bad:
            pool.quarantine(b)
            assert b not in pool.refcount, \
                f"corrupt block {b} still mapped after recovery"
            self.kv_arena.scrub_block(b)
        self.metrics.note_quarantine(len(bad))
        return bad

    # ---- fault-injection entry points (FaultPlane hooks) -------------
    def inject_instance_failure(self, kind: str, iid: int,
                                now: Optional[float] = None):
        """Kill one engine instance: the proxy reroutes its in-flight
        requests (retry-capped) and the next step's engine rounds release
        its slots / queued tasks / undelivered results."""
        now = time.monotonic() if now is None else now
        self.proxy.mark_unhealthy(kind, iid, now)

    def revive_instance(self, kind: str, iid: int):
        self.proxy.mark_healthy(kind, iid)

    def inject_kv_lost(self, rid: int, now: Optional[float] = None):
        """Lose one resident decode request's KV: its slots/blocks are
        released and the request reroutes through prefill, retry-capped."""
        now = time.monotonic() if now is None else now
        req = self.proxy.inflight.get(rid)
        for eng in self.decodes:
            eng.release(rid)
        if req is not None and req.phase == Phase.DECODE_RUNNING:
            self.proxy.on_decode_restart(req, now)

    def inject_handoff_drop(self, rid: int) -> bool:
        """Drop a parked handoff WITHOUT releasing its pool key — models a
        payload lost mid-rename. The orphan-handoff sweep reclaims the
        blocks; the request recovers via the kv-lost path at dispatch."""
        return self._pending_kv.pop(rid, None) is not None

    def _stop_tokens(self, req: Request) -> tuple:
        sp = req.sampling
        if sp is not None and sp.stop_token_ids:
            return sp.stop_token_ids
        # deprecated server-global default
        return (self.scfg.eos_token,) if self.scfg.eos_token >= 0 else ()

    def _note_token(self, req: Request, tok: int) -> Optional[str]:
        """Record one generated token; → finish reason or None. A request
        rerouted through on_decode_kv_lost regenerates from scratch — the
        draws are positional, so the replayed prefix is identical and the
        per-rid delivered counter suppresses re-streaming it."""
        req.output_tokens.append(tok)
        n = len(req.output_tokens)
        if n > self._emitted.get(req.rid, 0):
            self._fresh.setdefault(req.rid, []).append(tok)
            self._emitted[req.rid] = n
        if tok in self._stop_tokens(req):
            return "stop"
        if n >= req.max_tokens:
            return "length"
        return None

    def _record_finish(self, req: Request, reason: str):
        req.finish_reason = reason
        self._finish_info[req.rid] = (reason, len(req.output_tokens))
        self._emitted.pop(req.rid, None)
        self.metrics.add(req)

    def _flush_outputs(self) -> list[RequestOutput]:
        outs = []
        for rid, toks in self._fresh.items():
            reason, total = self._finish_info.pop(rid, (None, None))
            if total is None:
                total = self._emitted.get(rid, len(toks))
            outs.append(RequestOutput(rid, tuple(toks), reason is not None,
                                      reason, total))
        self._fresh.clear()
        self._finish_info.clear()
        outs.extend(self._events)
        self._events = []
        return outs

    def _drain_actions(self, now: float):
        admissions: dict[int, list[Request]] = {}
        for req, inst, stage in self.proxy.tick(now):
            if stage == "prefill":
                self.proxy.on_prefill_start(req, time.monotonic())
                self.prefills[inst.iid].start(req.rid, req.tokens,
                                              prefix_hint=req.prefix_match,
                                              params=req.sampling)
            else:
                admissions.setdefault(inst.iid, []).append(req)
        for iid, reqs in admissions.items():
            eng = self.decodes[iid]
            tnow = time.monotonic()
            items, live = [], []
            for r in reqs:
                kv = self._pending_kv.pop(r.rid, None)
                if kv is None:   # KV died with a failed decode instance
                    self.proxy.on_decode_kv_lost(r, tnow)
                    continue
                items.append((r.rid,) + kv)
                live.append(r)
            t0 = eng.stats["kv_transfer_bytes"]
            p0 = eng.stats["kv_transfer_bytes_padded"]
            with span("omni.decode.admit"):
                granted = eng.admit_batch(items)
            self.metrics.note_kv_transfer(
                eng.stats["kv_transfer_bytes"] - t0,
                eng.stats["kv_transfer_bytes_padded"] - p0)
            for req, item in zip(live, items):
                if granted[req.rid]:
                    self.proxy.on_decode_start(req, tnow)
                else:
                    self._pending_kv[req.rid] = item[1:]
                    self.proxy.on_decode_requeue(req, tnow)

    def _prefill_round(self):
        budget = self.scfg.prefill_tick_budget
        for iid, eng in enumerate(self.prefills):
            if not self.proxy.prefill[iid].healthy:
                # died mid-queue: proxy re-dispatches; abort() also frees
                # the tasks' pool blocks (a bare queue.clear would leak
                # prefill-phase block reservations)
                for t in list(eng.queue):
                    eng.abort(t.rid)
                # undelivered results die with the instance too: their
                # ("handoff", i) blocks would otherwise leak (the sweep is
                # the backstop; this is the prompt release)
                eng.drop_results()
                continue
            if not eng.has_work():
                continue
            with span("omni.prefill.round"):
                self._prefill_results(iid, eng.step(budget))

    def _prefill_results(self, iid: int, recs: list):
        """Hand one prefill engine's finished prompts to the proxy."""
        for rec in recs:
            req = self.proxy.inflight.get(rec.rid)
            tnow = time.monotonic()
            if req is None or req.prefill_instance != iid:
                # stale result for a re-dispatched rid
                self._release_handoff(rec.cache)
                continue
            if req.prefill_started is None:
                req.prefill_started = rec.prefill_started
            self.proxy.on_prefill_done(req, tnow, batch_time=rec.elapsed_s)
            # the first token materialized inside the engine round, not
            # when this bookkeeping runs
            self.proxy.on_first_token(req, rec.t_done or tnow)
            reason = self._note_token(req, rec.first_token)
            if reason:
                # stop token / max_tokens=1 at the FIRST token: retire
                # without ever admitting to decode (the never-admitted
                # handoff's arena blocks are released here)
                self._release_handoff(rec.cache)
                self.proxy.on_early_finish(req, tnow)
                self._record_finish(req, reason)
            else:
                self._pending_kv[req.rid] = (rec.cache, rec.first_token,
                                             rec.prompt_len, rec.reused,
                                             req.tokens, req.sampling)

    def _decode_round(self):
        for iid, eng in enumerate(self.decodes):
            if not self.proxy.decode[iid].healthy:
                for rid in list(eng.rid_slot):   # died: slots are garbage,
                    eng.release(rid)             # proxy re-routes the reqs
                eng.preempted.clear()
                continue
            with span("omni.decode.round"):
                self._decode_turn(iid, eng)
        self._step_count += 1
        self._maybe_placement_tick()
        self._maybe_sparsity_tick()

    def _decode_turn(self, iid: int, eng: DecodeEngine):
        """One decode engine's step and the bookkeeping of its tokens."""
        toks = eng.step()
        now = time.monotonic()
        finished = set()
        for rid, tok in toks.items():
            req = self.proxy.inflight.get(rid)
            if req is None or req.decode_instance != iid:
                eng.release(rid)             # done or re-routed elsewhere
                finished.add(rid)
                continue
            # a speculating engine emits a LIST per slot (≥ 1 token per
            # verify step); note each in order and stop at the first
            # finish reason — tokens past a mid-window stop are never
            # recorded or streamed, exactly as if decoded one at a time
            seq = tok if isinstance(tok, (list, tuple)) else (tok,)
            reason = None
            for t in seq:
                reason = self._note_token(req, t)
                if reason:
                    break
            if reason:
                finished.add(rid)
                eng.release(rid)
                self.proxy.on_decode_done(req, now,
                                          batch_time=eng.stats["busy_s"] /
                                          max(eng.stats["steps"], 1))
                self._record_finish(req, reason)
        for rid, cache_one, tok, pos in eng.preempted:
            req = self.proxy.inflight.get(rid)
            if rid in finished or req is None:
                continue
            self._pending_kv[rid] = (cache_one, tok, pos, 0, req.tokens,
                                     req.sampling)
            self.proxy.on_decode_preempt(req, now)
        eng.preempted.clear()

    def _maybe_sparsity_tick(self):
        """Drain the decode engines' device-side online-sparsity windows
        into the metrics at the monitor cadence (like the MoE activation
        window), so the STREAMING entry points (add_request/step/generate)
        report blocks_scored / blocks_attended / attn_mass_kept too — not
        just the closed-batch run() epilogue. One host sync per interval
        per sparse engine; no-op when online sparsity is off."""
        if self._step_count % max(self.scfg.placement_interval, 1) != 0:
            return
        if all(e.sparsity is None and e.spec_ctl is None
               for e in self.decodes):
            return
        with span("omni.monitor"):
            for eng in self.decodes:
                if eng.sparsity is not None:
                    sp = eng.take_sparsity_stats()
                    if sp is not None:
                        self.metrics.note_sparsity(*sp)
                if eng.spec_ctl is not None:
                    v = eng.take_spec_stats()
                    if v is not None:
                        self.metrics.note_spec(*v)

    # ---- OmniPlacement closed loop -----------------------------------
    def _maybe_placement_tick(self):
        """One monitor tick per interval on counts aggregated across every
        decode engine (the scheduler's activation window is time-indexed)."""
        if (self.placement_sched is None or
                self._step_count % max(self.scfg.placement_interval, 1) != 0):
            return
        with span("omni.monitor"):
            counts = None
            for eng in self.decodes:
                c = eng.take_moe_counts()       # fetch + reset the window
                if c is not None:
                    counts = c if counts is None else counts + c
            if counts is None:
                return
            plans = self.placement_sched.step(counts.sum(axis=0,
                                                         keepdims=True))
            if plans:
                self._apply_migration(plans[0])

    def _apply_migration(self, plan):
        """Rebuild MoE slot weights + tables for a new placement. The stack
        remap runs as one donated jit through the placement layer: expert
        rows gather from the canonical copy (rep_rank/rep_slot of the OLD
        tables) and scatter into the NEW slot layout, with out-shardings
        pinned to the stack's own specs so migration never perturbs the
        P("data", ..., "model") expert layout mid-stream. Compiled once;
        every later migration reuses it (rr/rs/new_se are traced args)."""
        if self._remap_stack is None:
            self._remap_stack = self.placement.donate_jit(
                remap_moe_slots, donate_argnums=(0,),
                out_specs=self.lm.specs()["stack"])

        old = self.tables
        rr = jnp.asarray(np.asarray(old["rep_rank"])[:, 0])
        rs = jnp.asarray(np.asarray(old["rep_slot"])[:, 0])
        new_se = np.asarray(plan.new_slot_expert)
        self.params["stack"] = self._remap_stack(
            self.params["stack"], rr, rs, jnp.asarray(new_se))
        self.tables = self.placement.replicate(
            tables_from_placement_from_slots(new_se))
        for eng in self.prefills + self.decodes:
            eng.tables = self.tables
        self.n_migrations += 1
        hist = self.placement_sched.history[-1] \
            if self.placement_sched.history else {}
        self.migration_log.append({
            "step": self._step_count,
            "b_before": float(hist.get("b", 0.0)),
            "b_after": float(hist.get("b_sim", 0.0))})

    # ------------------------------------------------------------------
    def audit_hot_loops(self, require_called: bool = False):
        """ContractGuard layer-2 entry point (see docs/analysis.md): jaxpr/
        lowering audit over every hot-loop jit this server's placement
        registered through donate_jit. Call post-warmup — entries capture
        their abstract argument signatures at first real call, and the
        audit re-traces from those (no live buffers touched). Returns an
        `AuditReport`; `report.ok()` is the pass/fail bit."""
        from repro.analysis.jaxpr_audit import audit_placement
        return audit_placement(self.placement,
                               require_called=require_called)

    # ------------------------------------------------------------------
    def run(self, requests: list, max_wall_s: float = 300.0,
            arrivals: Optional[list[float]] = None):
        """Closed-batch driver over the streaming primitives.
        requests: [(prompt_tokens, max_tokens:int)] or
        [(prompt_tokens, SamplingParams)]; arrivals: per-request offsets
        from t=0 (None → all at t=0, closed-loop pressure). Returns the
        metrics summary. Greedy int-budget items reproduce the pre-API
        outputs bit-exactly."""
        t_start = time.monotonic()
        todo = sorted(
            ((0.0 if arrivals is None else arrivals[i], i, p, spec)
             for i, (p, spec) in enumerate(requests)))
        k = 0
        expired = False
        while k < len(todo) or self.proxy.inflight:
            now = time.monotonic()
            if now - t_start >= max_wall_s:
                expired = True      # reported: n_done < n_requests
                break
            while k < len(todo) and now - t_start >= todo[k][0]:
                offset, i, prompt, spec = todo[k]
                params = spec if isinstance(spec, SamplingParams) else \
                    SamplingParams(max_tokens=int(spec))
                try:
                    # arrival at its scheduled time, not when the loop got
                    # round to it: a slow step's queueing counts in TTFT
                    self._submit(i, tuple(prompt), params, t_start + offset)
                except BackpressureError:
                    pass        # shed (counted in metrics.n_shed)
                k += 1
            if not self.proxy.inflight and k < len(todo):
                # nothing in flight and the next arrival is in the future:
                # sleep instead of busy-spinning on time.monotonic()
                wait = (t_start + todo[k][0]) - time.monotonic()
                if wait > 0:
                    nap = min(wait, self.scfg.idle_sleep_s)
                    time.sleep(nap)
                    self._idle_slept_s += nap
                    continue
            self.step(now)
        wall = time.monotonic() - t_start
        for eng in self.decodes:
            # drain the device-side online-sparsity window (no-op when off)
            # so the summary reports blocks_scored / blocks_attended /
            # attn_mass_kept next to the wall-clock columns
            sp = eng.take_sparsity_stats()
            if sp is not None:
                self.metrics.note_sparsity(*sp)
            # same for the speculation window (no-op when spec is off)
            v = eng.take_spec_stats()
            if v is not None:
                self.metrics.note_spec(*v)
        summary = self.metrics.summary(wall)
        summary["wall_s"] = wall
        summary["n_requests"] = len(requests)
        summary["wall_expired"] = expired
        summary["n_migrations"] = self.n_migrations
        summary["migration_log"] = list(self.migration_log)
        summary["idle_slept_s"] = self._idle_slept_s
        summary["n_handoffs_swept"] = self.n_handoffs_swept
        if self.faults is not None:
            summary["faults_injected"] = dict(self.faults.injected)
        summary["prefill_stats"] = [e.stats for e in self.prefills]
        summary["decode_stats"] = [e.stats for e in self.decodes]
        return summary


def remap_moe_slots(stack, rr, rs, new_se):
    """The MoE slot tensors of a stack's params re-slotted for a new
    placement: expert e's rows come from (rank rr[e], slot rs[e]) of the
    current layout and land in every slot new_se maps to e. Stacked layers
    are remapped one at a time, in place: gathering the canonical rows puts
    every expert of a layer on each device, so a whole stack at once would
    hold n_rep layers of all experts on every device (24 layers of
    qwen2-moe-a2.7b: 8.3 GB per tensor)."""
    def one(w):              # [R, s, ...] -> [R, s, ...]
        return moe_mod.slots_from_canonical(w[rr, rs], new_se)

    def stacked(w):          # [n_rep, R, s, ...]
        return jax.lax.fori_loop(
            0, w.shape[0], lambda i, w: w.at[i].set(one(w[i])), w)

    def layer(p, is_stacked):
        if "moe_w1" not in p:
            return p
        p = dict(p)
        for k in ("moe_w1", "moe_w3", "moe_w2"):
            p[k] = stacked(p[k]) if is_stacked else one(p[k])
        return p
    return {"period": tuple(layer(p, True) for p in stack["period"]),
            "rem": tuple(layer(p, False) for p in stack["rem"])}
