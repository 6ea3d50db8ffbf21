"""Chunked / paged prefill engine (the P side of PD disaggregation).

PrefillEngine processes prompts in fixed-size token chunks (jit'd once per
chunk bucket, cache threaded between chunks through LM.prefill_resume) and
schedules queued prompts shortest-remaining-first at chunk granularity, so a
short prompt never sits behind a long in-flight prefill. With a KVArena the
prefill phase is itself PAGED: each chunk reserves real KVPool blocks and
writes its KV straight into the per-layer block arenas through a per-task
block table (kernels/paged_prefill.py / paged_prefill_attention), so an
in-flight prompt pins blocks ∝ its length — never a dense max_len cache —
and a reservation the pool cannot serve DEFERS the task (backpressure)
instead of over-committing HBM. Completed prefixes land in a radix-backed
PrefixKVStore as refcounted block lists sized by real bytes: a later prompt
sharing an N-token prefix maps the entry's full blocks (copying only the
partial tail) and resumes prefill at token N.

Built through a `DevicePlacement`: every jit routes through its donate_jit
choke point, and the paged chunk jit pins the composed (private ∪ arena)
cache's PartitionSpec tree as out-shardings so the arena stays TP-sharded
through the donated write-back.
"""
from __future__ import annotations

import time
from collections import deque
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.proxy.params import GREEDY, SamplingParams, device_row
from repro.core.proxy.radix import RadixTree
from repro.models.lm import LM
from jax.sharding import PartitionSpec as P

from repro.models.stack import (_drop_entries, alloc_cache,
                                alloc_prefill_private_cache, full_attn_layer,
                                merge_arena_cache, split_arena_cache)
from repro.serving.arena import BlockHandoff, KVArena, _bucket, _pow2_floor
from repro.serving.kvpool import PrefixKVStore, _pytree_bytes
from repro.serving.placement import DevicePlacement
from repro.serving.sampling import sample_tokens
from repro.serving.spans import span


# ======================================================================
@dataclass
class PrefillTask:
    rid: int
    prompt: tuple
    cache: object = None              # threaded B=1 cache (None until started)
    logits: object = None             # last-token logits of the latest chunk
    cursor: int = 0                   # tokens resident (incl. reused prefix)
    reused: int = 0                   # prefix tokens resumed from the store
    snap: int = 0                     # snapshot boundary (shared-prefix hint)
    params: SamplingParams = GREEDY   # first-token decoding config
    t_start: float = 0.0
    prefill_started: Optional[float] = None   # first picked in a round
    compute_s: float = 0.0            # pure prefill compute (excl. queue wait)
    handoff: object = None            # BlockHandoff once finished (paged)

    @property
    def remaining(self) -> int:
        return len(self.prompt) - self.cursor


@dataclass
class PrefillResult:
    rid: int
    cache: object
    first_token: int
    prompt_len: int
    reused: int
    elapsed_s: float                  # prefill compute time (EWMA batch time)
    t_done: float = 0.0               # wall time the first token materialized
    prefill_started: Optional[float] = None   # wall time of the first pick


@dataclass
class PrefillEngine:
    _next_handoff_id = 0              # shared-pool-unique handoff keys
    lm: LM
    params: dict
    tables: Optional[dict]
    max_len: int
    chunk_tokens: int = 64            # target chunk size (TTFT/TPOT knob)
    enable_chunked: bool = True
    allow_partial_reuse: bool = True
    cache_cap: int = 32               # PrefixKVStore entries
    cache_cap_bytes: Optional[int] = None   # PrefixKVStore byte cap (LRU)
    tree: Optional[RadixTree] = None  # share the proxy's per-instance tree
    arena: Optional[KVArena] = None   # shared paged-KV runtime → paged mode
    block_size: int = 16              # accounting granularity (dense mode)
    placement: Optional[DevicePlacement] = None
    stats: dict = field(default_factory=lambda: {
        "prefills": 0, "cache_hits": 0, "prefix_hits": 0, "reused_tokens": 0,
        "tokens": 0, "chunks": 0, "busy_s": 0.0, "host_fetches": 0,
        "blocks_mapped": 0, "prefill_kv_peak_blocks": 0, "defers": 0})

    def __post_init__(self):
        if self.placement is None:
            self.placement = (self.arena.placement if self.arena is not None
                              else DevicePlacement.of(self.lm.mesh))
        pl = self.placement
        self._fn = pl.donate_jit(self._prefill)
        self._resume = pl.donate_jit(self._resume_impl, donate_argnums=(2,),
                                     static_argnums=(5,))
        self._first = pl.donate_jit(self._first_impl)
        self.queue: deque[PrefillTask] = deque()
        self._ready: list[PrefillResult] = []
        sup, limit = self.lm.chunked_prefill_support
        self.chunk = _pow2_floor(max(min(self.chunk_tokens, limit), 1))
        self.chunked = bool(self.enable_chunked and sup and self.chunk >= 8)
        # paged prefill rides the chunked machinery (blocks grow per chunk);
        # with chunking unsupported the engine falls back to dense prefill
        # and the decode engine's dense-scatter admission compat path
        self.paged = bool(self.arena is not None and self.chunked)
        if self.paged:
            self.block_size = self.arena.block_size
            cfg, plan = self.lm.cfg, self.lm.plan
            # pin the composed chunk output: private dense specs (full-attn
            # dropped) ∪ arena specs, with replicated last-token logits
            private = _drop_entries(
                cfg, plan, pl.dense_cache_specs(cfg, plan, 1, self.max_len),
                drop_full=True)
            merged = merge_arena_cache(cfg, plan, private,
                                       pl.arena_specs(cfg, plan,
                                                      quant=self.arena.quant))
            self._resume_paged = pl.donate_jit(
                self._resume_paged_impl, donate_argnums=(2,),
                out_specs=(merged, P()))
        self.store = PrefixKVStore(
            self.tree, self.cache_cap,
            pool=self.arena.pool if self.paged else None,
            capacity_bytes=self.cache_cap_bytes)
        if self.paged:
            self.arena.reclaimers.append(self.store.evict_for_blocks)

    # ---- jit bodies --------------------------------------------------
    def _prefill(self, params, tokens, true_len, tables):
        cache, logits, _ = self.lm.prefill(params, {"tokens": tokens},
                                           max_len=self.max_len, tables=tables,
                                           true_len=true_len)
        return cache, logits

    def _resume_impl(self, params, tokens, cache, chunk_len, tables,
                     attend_limit):
        cache, logits, _ = self.lm.prefill_resume(
            params, {"tokens": tokens}, cache, max_len=self.max_len,
            tables=tables, chunk_len=chunk_len, attend_limit=attend_limit)
        return cache, logits

    def _resume_paged_impl(self, params, tokens, cache, chunk_len, tables,
                           tbl_row):
        """One paged chunk: full-attention cache leaves are the shared
        arenas, the chunk's KV is written straight into the tabled blocks
        (no dense max_len cache exists anywhere on this path)."""
        cache, logits, _ = self.lm.prefill_resume(
            params, {"tokens": tokens}, cache, max_len=self.max_len,
            tables=tables, chunk_len=chunk_len, block_tables=tbl_row)
        return cache, logits

    def _first_impl(self, logits_tuple, temp, tk, tp, keys, fold):
        """Fused first-token sampling over the stacked last-token logits of
        a batch of finished prefills (pow2-padded)."""
        logits = jnp.concatenate(logits_tuple, axis=0)
        return sample_tokens(logits, temp, tk, tp, keys, fold)

    # ---- paged-KV helpers --------------------------------------------
    @staticmethod
    def _pf_key(rid: int) -> tuple:
        return ("prefill", rid)

    def _resize_full_attn(self, cache, length: int, copy_rest: bool = False):
        """Slice or zero-pad the full-attention KV leaves of a dense B=1
        cache to `length` tokens (the prefix-store sizing fix: stored
        prefixes pin prefix-length KV, not a max_len allocation). Ring /
        mamba leaves are untouched (bounded) unless copy_rest — then they
        are jnp.copy'd so the snapshot survives chunk-to-chunk donation."""
        cfg, plan = self.lm.cfg, self.lm.plan

        def one(spec, entry, stacked):
            if entry is None:
                return None
            if not full_attn_layer(cfg, spec):
                return jax.tree.map(jnp.copy, entry) if copy_rest else entry
            ax = 2 if stacked else 1

            def f(x):
                W = x.shape[ax]
                if W > length:
                    idx = [slice(None)] * x.ndim
                    idx[ax] = slice(0, length)
                    return x[tuple(idx)]
                if W < length:
                    pad = [(0, 0)] * x.ndim
                    pad[ax] = (0, length - W)
                    return jnp.pad(x, pad)
                return jnp.copy(x) if copy_rest else x
            return {kk: f(vv) for kk, vv in entry.items()}

        return {"period": tuple(one(s, cache["period"][i], True)
                                for i, s in enumerate(plan.period)),
                "rem": tuple(one(s, cache["rem"][i], False)
                             for i, s in enumerate(plan.rem)),
                "pos": jnp.copy(cache["pos"]) if copy_rest else cache["pos"]}

    def _grow_blocks(self, task: PrefillTask, cl: int) -> bool:
        """Reserve pool blocks for the next `cl` chunk tokens. On
        exhaustion, reclaim shared cache (LRU store entries) and retry;
        still short → False (the caller defers this task — backpressure
        instead of HBM over-commit)."""
        pool, key = self.arena.pool, self._pf_key(task.rid)
        target = task.cursor + cl

        def attempt():
            if key in pool:
                return pool.extend(key, task.cursor, target)
            return pool.allocate(key, target)

        got = attempt()
        if got is None:
            held = len(pool.owned(key)) if key in pool else 0
            need = pool.blocks_for(target) - held - pool.free_blocks
            self.arena.reclaim(max(need, 1))
            got = attempt()
        return got is not None

    def _table_row(self, rid: int) -> jnp.ndarray:
        nb = -(-self.max_len // self.block_size)
        row = np.zeros((1, nb), np.int32)
        owned = self.arena.pool.owned(self._pf_key(rid))
        row[0, :len(owned)] = owned
        return jnp.asarray(row)

    def _store_put_paged(self, task: PrefillTask, n: int,
                         copy_private: bool) -> None:
        """Publish the first `n` tokens of a task as a store entry: the
        covering blocks are adopted (refcounted) by the store — zero copy —
        and only the bounded private leaves are snapshotted. Entry size is
        the REAL resident bytes, so LRU eviction can tell a 16-token prefix
        from a 2048-token one."""
        pool = self.arena.pool
        blocks = pool.owned(self._pf_key(task.rid))[:pool.blocks_for(n)]
        priv = jax.tree.map(jnp.copy, task.cache) if copy_private \
            else task.cache
        nbytes = (len(blocks) * self.arena.block_nbytes + _pytree_bytes(priv)
                  + _pytree_bytes(task.logits))
        self.store.put(task.prompt[:n], priv, task.logits, blocks=blocks,
                       nbytes=nbytes)

    def _store_put(self, task: PrefillTask, n: int, copy: bool) -> None:
        """Publish the first `n` tokens of a task as a store entry; `copy`
        snapshots the task's own state, which its later chunks donate."""
        with span("omni.prefill.store", rid=task.rid, tokens=n):
            if self.paged:
                self._store_put_paged(task, n, copy_private=copy)
            else:
                # prefix-length entry (sizing fix): the full-attention KV
                # sliced to the boundary instead of pinning a max_len copy
                self.store.put(
                    task.prompt[:n],
                    self._resize_full_attn(
                        task.cache, min(_bucket(n, lo=8), self.max_len),
                        copy_rest=copy),
                    task.logits)

    def _release_result(self, rec: PrefillResult) -> None:
        """Drop an undelivered result (supersede/abort): a paged handoff
        still owns pool blocks that nobody will ever admit."""
        if isinstance(rec.cache, BlockHandoff):
            self.arena.pool.release(rec.cache.key)

    def _note_peak(self, task: PrefillTask) -> None:
        """Work-based memory metric: peak KV blocks pinned by a SINGLE
        in-flight prefill. Paged tasks grow per chunk, so the peak is
        blocks_for(prompt_len); a dense task pins a blocks_for(max_len)
        cache from its first chunk regardless of prompt length — exactly
        the prefill-phase over-commit paged prefill removes."""
        if self.paged:
            held = len(self.arena.pool.owned(self._pf_key(task.rid)))
        else:
            held = -(-self.max_len // self.block_size)
        if held > self.stats["prefill_kv_peak_blocks"]:
            self.stats["prefill_kv_peak_blocks"] = held

    # ---- scheduling --------------------------------------------------
    def start(self, rid: int, prompt: tuple, prefix_hint: int = 0,
              params: Optional[SamplingParams] = None) -> None:
        """Enqueue a prompt. Exact store hits complete immediately (drained
        by the next step()); partial hits resume at the stored boundary.
        prefix_hint (the proxy's Match_P, computed before self-insertion)
        marks a prefix shared with other prompts: the engine snapshots its
        cache at that boundary so later sharers can resume there."""
        # a re-dispatch of the same rid (instance fail/recover) supersedes any
        # queued task or undelivered result — otherwise both complete and the
        # proxy sees duplicate first tokens
        for t in list(self.queue):
            if t.rid == rid:
                self.queue.remove(t)
                if self.paged:
                    self.arena.pool.release(self._pf_key(rid))
        for r in self._ready:
            if r.rid == rid:
                self._release_result(r)
        self._ready = [r for r in self._ready if r.rid != rid]
        task = PrefillTask(rid, tuple(prompt), params=params or GREEDY,
                           t_start=time.monotonic())
        if (self.chunked and self.allow_partial_reuse
                and 8 <= prefix_hint < len(task.prompt)):
            task.snap = prefix_hint
        with span("omni.prefill.resume", rid=rid):
            self._try_resume(task)
        self.queue.append(task)

    def _try_resume(self, task: PrefillTask) -> None:
        """Resume from the deepest stored prefix (exact hits: adopt whole)."""
        if self.paged:
            self._try_resume_paged(task)
            return
        n, cache, logits = self.store.lookup(task.prompt)
        if cache is None or n <= task.cursor:
            return
        if n == len(task.prompt):
            # stored caches are prefix-trimmed: pad the full-attention KV
            # back to the engine's max_len working shape (ring/mamba leaves
            # are shared — an adopted whole is never donated downstream)
            task.cache, task.logits = \
                self._resize_full_attn(cache, self.max_len), logits
            task.cursor = task.reused = n
            return
        if self.chunked and self.allow_partial_reuse:
            # copy — the threaded cache is donated chunk-to-chunk and must
            # not eat the store's buffers
            task.cache = self._resize_full_attn(cache, self.max_len,
                                                copy_rest=True)
            task.logits = logits
            task.cursor = task.reused = n
            self.stats["prefix_hits"] += 1
            self.stats["reused_tokens"] += n

    def _try_resume_paged(self, task: PrefillTask) -> None:
        """Paged resume: map the entry's FULL prefix blocks into the task's
        table (refcount++, zero copy); a partial tail block is copied into
        a private block — its content diverges as the task appends. Exact
        hits adopt the same way (the tail copy keeps two adopters of one
        prompt from clobbering each other's decode-time appends)."""
        ent = self.store.lookup_entry(task.prompt)
        if ent is None or ent.n <= task.cursor or ent.blocks is None:
            return
        if not (self.allow_partial_reuse or ent.n == len(task.prompt)):
            return
        pool, key = self.arena.pool, self._pf_key(task.rid)
        if key in pool:                 # mid-flight deepening is unsound
            return
        n = ent.n
        full = n // pool.block_size
        # pin the entry's blocks for the duration: reclaim-under-pressure
        # below may evict THIS entry, and without the pin its released
        # blocks would hit the free list while we are about to map them as
        # `shared` (and read the tail for the copy) — allocator corruption
        pin = ("resume-pin", task.rid)
        pool.adopt(pin, ent.blocks)
        try:
            tbl = pool.allocate(key, n, shared=ent.blocks[:full])
            if tbl is None:
                self.arena.reclaim(pool.blocks_for(n) - full)
                tbl = pool.allocate(key, n, shared=ent.blocks[:full])
                if tbl is None:
                    return              # backpressure: prefill from scratch
            if pool.blocks_for(n) > full:   # partial tail → copy-on-write
                self.arena.copy_block(ent.blocks[full], tbl[full])
        finally:
            pool.release(pin)
        # private leaves are donated chunk-to-chunk: always copy
        task.cache = jax.tree.map(jnp.copy, ent.cache)
        task.logits = ent.logits
        task.cursor = task.reused = n
        self.stats["blocks_mapped"] += full
        if n < len(task.prompt):
            self.stats["prefix_hits"] += 1
            self.stats["reused_tokens"] += n

    def has_work(self) -> bool:
        return bool(self.queue or self._ready)

    def abort(self, rid: int) -> bool:
        """Drop a queued / in-flight / completed-but-undelivered prompt.
        The task's private cache is released to the GC and its pool blocks
        (paged) are released; store snapshots it already published stay —
        they are shared cache, not request state (their blocks are
        refcounted under the store's own key)."""
        hit = False
        for t in list(self.queue):
            if t.rid == rid:
                self.queue.remove(t)
                hit = True
        if self.paged:
            self.arena.pool.release(self._pf_key(rid))
        n0 = len(self._ready)
        for r in self._ready:
            if r.rid == rid:
                self._release_result(r)
        self._ready = [r for r in self._ready if r.rid != rid]
        return hit or len(self._ready) != n0

    def drop_results(self) -> int:
        """Discard every completed-but-undelivered result, releasing paged
        handoff blocks (instance-death recovery: a dead engine's results
        will never be drained by the server loop — without this their
        ("handoff", i) pool keys leak). → results dropped."""
        n = len(self._ready)
        for r in self._ready:
            self._release_result(r)
        self._ready = []
        return n

    def step(self, token_budget: int = 1 << 30) -> list[PrefillResult]:
        """Run up to `token_budget` tokens of prefill work; → completed
        prompts. Chunked mode schedules shortest-remaining-first at chunk
        granularity (a short prompt preempts an in-flight long prefill at
        the next chunk boundary); unchunked mode is the pre-chunking engine:
        FIFO, one whole prompt per call. Paged tasks that cannot grow their
        block reservation are DEFERRED for the round (stats.defers) rather
        than over-committing — they retry when decode/store releases free
        blocks."""
        done, budget = self._ready, token_budget
        self._ready = []
        fresh: list[PrefillTask] = []
        blocked: set[int] = set()
        t0 = time.monotonic()
        while budget > 0:
            cands = [t for t in self.queue if t.rid not in blocked]
            if not cands:
                break
            task = (min(cands, key=lambda t: t.remaining)
                    if self.chunked else cands[0])
            if task.prefill_started is None:
                task.prefill_started = time.monotonic()
            if task.cursor == 0:
                # entries stored since enqueue (e.g. a queued sharer's
                # snapshot) are visible to tasks that have not started
                with span("omni.prefill.resume", rid=task.rid):
                    self._try_resume(task)
            if task.remaining > 0:
                ran = (self._run_chunk(task, min(budget, self.chunk))
                       if self.chunked else self._run_full(task))
                if ran == 0 and task.remaining > 0:
                    blocked.add(task.rid)       # pool backpressure: defer
                    continue
                budget -= ran
            if task.remaining == 0:
                self.queue.remove(task)
                fresh.append(self._finish(task))
        if fresh:
            done.extend(self._emit(fresh))
        self.stats["busy_s"] += time.monotonic() - t0
        return done

    def _run_chunk(self, task: PrefillTask, budget: int) -> int:
        t0 = time.monotonic()
        cl = min(self.chunk, task.remaining, max(budget, 1))
        if task.cursor < task.snap:
            cl = min(cl, task.snap - task.cursor)   # land on the boundary
        with span("omni.prefill.chunk", rid=task.rid, tokens=cl):
            if not self._dispatch_chunk(task, cl):
                return 0
        self._note_peak(task)
        if task.cursor == task.snap and \
                self.store.lookup(task.prompt[:task.snap])[0] != task.snap:
            self._store_put(task, task.snap, copy=True)
        task.compute_s += time.monotonic() - t0
        return cl

    def _dispatch_chunk(self, task: PrefillTask, cl: int) -> bool:
        """Reserve blocks for `cl` tokens and enqueue their chunk program;
        → False where the pool could not grow (the task defers)."""
        if self.paged and not self._grow_blocks(task, cl):
            self.stats["defers"] += 1
            return False
        if task.cache is None:
            task.cache = (alloc_prefill_private_cache(
                self.lm.cfg, self.lm.mesh, self.lm.plan, self.max_len)
                if self.paged else
                alloc_cache(self.lm.cfg, self.lm.mesh, self.lm.plan, 1,
                            self.max_len))
        S = min(_bucket(cl, lo=8), self.chunk)
        toks = list(task.prompt[task.cursor:task.cursor + cl]) + [0] * (S - cl)
        if self.paged:
            # chunk KV is written straight into the arena blocks through
            # the task's table — the composed cache's full-attention leaves
            # ARE the shared arenas (donated and written back)
            composed = merge_arena_cache(self.lm.cfg, self.lm.plan,
                                         task.cache, self.arena.kv)
            composed, task.logits = self._resume_paged(
                self.params, jnp.asarray([toks], jnp.int32), composed,
                jnp.int32(cl), self.tables, self._table_row(task.rid))
            task.cache, self.arena.kv = split_arena_cache(
                self.lm.cfg, self.lm.plan, composed)
        else:
            # attend_limit=0: one trace per chunk bucket. (Passing a pow2
            # prefix bound trims attention flops but multiplies trace
            # count — a win on accelerators, a compile-stall hazard on the
            # CPU-real path.)
            task.cache, task.logits = self._resume(
                self.params, jnp.asarray([toks], jnp.int32), task.cache,
                jnp.int32(cl), self.tables, 0)
        task.cursor += cl
        self.stats["tokens"] += cl
        self.stats["chunks"] += 1
        return True

    def _run_full(self, task: PrefillTask) -> int:
        t0 = time.monotonic()
        S = len(task.prompt)
        # lo=8: same bucket floor as the chunked path — a short prompt must
        # not compile a gratuitous extra trace just because it arrived at
        # an unchunked engine
        pad = min(_bucket(S, lo=8), self.max_len) - S
        toks = jnp.asarray([list(task.prompt) + [0] * pad], jnp.int32)
        with span("omni.prefill.chunk", rid=task.rid, tokens=S):
            task.cache, task.logits = self._fn(self.params, toks,
                                               jnp.int32(S), self.tables)
        task.cursor = S
        self.stats["tokens"] += S
        self._note_peak(task)
        task.compute_s += time.monotonic() - t0
        return S

    def _finish(self, task: PrefillTask) -> PrefillTask:
        """Store bookkeeping for a completed prompt. The first token is NOT
        sampled here: finished tasks of one engine round are sampled in a
        single fused call (`_emit`) — the per-record `int(jnp.argmax(...))`
        host sync is gone. Paged tasks turn into a BlockHandoff: pool
        ownership moves from the task to the handoff record, which
        admission later renames to the decode rid — zero copy end to end."""
        L = len(task.prompt)
        if task.reused == L:                    # whole prompt adopted
            self.stats["cache_hits"] += 1
        else:
            self.stats["prefills"] += 1
            self._store_put(task, L, copy=False)
        if self.paged:
            pool, key = self.arena.pool, self._pf_key(task.rid)
            # class-level counter: several engines share one pool (arena),
            # so handoff keys must be unique ACROSS engines — per-engine
            # counters collide at ("handoff", 0)
            hkey = ("handoff", PrefillEngine._next_handoff_id)
            PrefillEngine._next_handoff_id += 1
            blocks = tuple(pool.transfer(key, hkey))
            task.handoff = BlockHandoff(hkey, blocks, task.cache, L)
        return task

    def _emit(self, tasks: list) -> list[PrefillResult]:
        with span("omni.prefill.sample", n=len(tasks)):
            toks = self.sample_first([t.logits for t in tasks],
                                     [t.params for t in tasks],
                                     [t.rid for t in tasks],
                                     [len(t.prompt) for t in tasks])
        t_done = time.monotonic()
        return [PrefillResult(t.rid, t.handoff if t.handoff is not None
                              else t.cache, int(tok), len(t.prompt),
                              t.reused, t.compute_s, t_done,
                              t.prefill_started)
                for t, tok in zip(tasks, toks)]

    def sample_first(self, logits_list, params_list, rids, folds
                     ) -> np.ndarray:
        """Sample the first token for a batch of finished prompts under
        each one's SamplingParams in ONE jit call + ONE host fetch
        (pow2-padded to bound retraces). logits_list: [1, V] arrays;
        folds: context lengths (= prompt lengths)."""
        n = len(logits_list)
        npad = _bucket(n, lo=1)
        logits = tuple(logits_list) + (logits_list[-1],) * (npad - n)
        rows = [device_row(p, r) for p, r in zip(params_list, rids)]
        rows += [rows[-1]] * (npad - n)
        temp = jnp.asarray([r[0] for r in rows], jnp.float32)
        tk = jnp.asarray([r[1] for r in rows], jnp.int32)
        tp = jnp.asarray([r[2] for r in rows], jnp.float32)
        keys = jnp.asarray(np.stack([r[3] for r in rows]))
        fold = jnp.asarray(list(folds) + [folds[-1]] * (npad - n), jnp.int32)
        out = np.asarray(self._first(logits, temp, tk, tp, keys, fold))
        self.stats["host_fetches"] += 1
        return out[:n]

    # ---- blocking back-compat API ------------------------------------
    def process(self, prompt: tuple) -> tuple:
        """→ (cache B=1, first_token:int, elapsed_s). Runs the prompt to
        completion (chunked underneath when supported)."""
        t0 = time.monotonic()
        self.start(-1, tuple(prompt))
        while True:
            recs = self.step()
            self._ready.extend(r for r in recs if r.rid != -1)
            for rec in recs:
                if rec.rid == -1:
                    return rec.cache, rec.first_token, time.monotonic() - t0
