"""Continuous-batch decode engine (the D side of PD disaggregation).

DecodeEngine admits pending caches in one donated jit call per batch, keeps
slot state (pos / cur_tok / active) device-side so the hot step has a single
[n_slots] host fetch (the sampled tokens), and masks inactive slots. With
paged=True (default) attention KV lives in physically paged per-layer
arenas; the decode step reads only resident blocks through per-slot block
tables, and a step that cannot grow its allocation preempts the request
(cache gathered back out of the arenas for re-admission) after LRU store
reclaim fails, instead of over-committing HBM. See docs/serving.md.

Built through a `DevicePlacement`: the hot step jit and both admission jits
route through its donate_jit choke point with the composed (private ∪
arena) cache specs and the replicated slot-state specs pinned as
out-shardings, so on a TP/EP mesh the donated state keeps its layout call
to call and the jit argument cache never churns.
"""
from __future__ import annotations

import time
from dataclasses import dataclass, field
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.core.proxy.params import SamplingParams, device_row
from repro.models import attention as attn_mod
from repro.models.lm import LM
from repro.models.stack import (alloc_cache, alloc_paged_private_cache,
                                cache_struct, cache_window, full_attn_layer,
                                merge_arena_cache, ring_block_count,
                                split_arena_cache)
from repro.serving.arena import (BlockHandoff, KVArena, _bucket,
                                 blocks_to_dense_kv, dense_kv_to_blocks,
                                 kv_bytes)
from repro.serving.kvpool import KVPool
from repro.serving.placement import DevicePlacement
from repro.serving.sampling import sample_tokens
from repro.serving.sparsity import SparsityController
from repro.serving.spans import span
from repro.serving.spec import SpecConfig, SpecController
from repro.serving.stats import drain_accumulator


# ======================================================================
@dataclass
class DecodeEngine:
    """Continuous-batch decode engine.

    paged=True (default): attention KV lives in physically paged per-layer
    arenas. Admission allocates real blocks from the KVPool and scatters the
    incoming B=1 dense cache into them (prefix-sharing admissions map the
    lender's full prefix blocks instead of writing them — only the partial
    tail block and the suffix are copied); each decode step writes the new
    token's K/V through the per-slot block table and attends over resident
    blocks only; preemption extracts the dense cache back out of the arenas
    and releases the blocks (refcounted — shared blocks survive until their
    last mapper leaves). paged=False preserves the slot-dense layout with
    accounting-only admission control.
    """
    lm: LM
    params: dict
    tables: Optional[dict]
    n_slots: int
    max_len: int
    hbm_budget_bytes: int = 1 << 34
    kv_blocks: Optional[int] = None   # explicit pool size (tests/benchmarks)
    paged: bool = True                # physically paged attention KV
    block_size: int = 16
    arena: Optional[KVArena] = None   # shared arena (co-located prefill)
    placement: Optional[DevicePlacement] = None
    spec: Optional[SpecConfig] = None   # model-free speculative decoding
    spec_radix: Optional[object] = None  # proxy RadixTree for draft lookup
    stats: dict = field(default_factory=lambda: {
        "steps": 0, "tokens": 0, "busy_s": 0.0, "kv_transfer_bytes": 0,
        "kv_transfer_bytes_padded": 0, "handoff_copy_bytes": 0,
        "admits": 0, "preemptions": 0,
        "blocks_touched": 0, "blocks_shared": 0, "blocks_fresh": 0,
        "host_fetches": 0})

    def __post_init__(self):
        cfg = self.lm.cfg
        if self.placement is None:
            self.placement = (self.arena.placement if self.arena is not None
                              else DevicePlacement.of(self.lm.mesh))
        pl = self.placement
        if self.paged:
            if self.arena is None:
                if self.kv_blocks is None:
                    # capacity parity with the dense layout: every slot can
                    # run to max_len; the pool turns that into admission
                    # flexibility
                    self.kv_blocks = self.n_slots * \
                        -(-self.max_len // self.block_size)
                self.arena = KVArena.build(self.lm, self.kv_blocks,
                                           self.block_size, placement=pl)
            self.block_size = self.arena.block_size
            self.kv_blocks = self.arena.pool.n_blocks
        self.max_blocks = -(-self.max_len // self.block_size)
        self.sparsity = None
        if self.paged:
            # engine-private side only: per-slot ring arenas + non-attention
            # state; the full-attention arenas live in the (possibly shared)
            # KVArena and are composed in around every jit call
            self.cache = alloc_paged_private_cache(
                cfg, self.lm.mesh, self.lm.plan, self.n_slots, self.max_len,
                self.block_size)
            self.tables_h = np.zeros((self.n_slots, self.max_blocks), np.int32)
            self._tbl_dev = jnp.asarray(self.tables_h)
            self._tbl_bucket = self.max_blocks
            self._tbl_dirty = False
            # online top-k block selection (OmniAttn dynamic sparsity):
            # resolved once from cfg.omniattn — the step jit reads the same
            # config, so controller and trace always agree
            self.sparsity = SparsityController.from_model(
                cfg, self.lm.plan, self.block_size, self.max_blocks)
            if self.sparsity is not None:
                self.stats.update(SparsityController.stats_keys())
        else:
            self.cache = alloc_cache(cfg, self.lm.mesh, self.lm.plan,
                                     self.n_slots, self.max_len)
            if self.kv_blocks is None:
                per_slot = kv_bytes(self.cache) // max(self.n_slots, 1)
                budget = max(self.hbm_budget_bytes // max(per_slot, 1),
                             self.n_slots) * 4
                # the accounting pool only needs to never constrain below the
                # slot-dense physical capacity — don't materialize a free
                # list for the raw HBM-budget block count (~1e5 ids)
                self.kv_blocks = min(budget,
                                     self.n_slots * self.max_blocks * 4)
        self.pool = self.arena.pool if self.paged else \
            KVPool(n_blocks=self.kv_blocks, block_size=self.block_size)
        # model-free speculative decoding (SpecPlane): drafting state lives
        # host-side in the controller; the batched verify runs as ONE extra
        # donated jit over [n_slots, k+1] window positions
        self.spec_ctl = SpecController.from_model(
            self.lm, self.spec, sparsity=self.sparsity, radix=self.spec_radix)
        if self.spec_ctl is not None:
            if not self.paged:
                raise ValueError("speculative decoding requires paged "
                                 "attention KV (block/summary rollback is "
                                 "defined on the paged plane)")
            self.stats.update(SpecController.stats_keys())
        # PD transfer-cost metering constants: a B=1 dense handoff cache is
        # `_dense_kv_nbytes` regardless of prompt length (the padded figure
        # the old meter charged); the TRUE payload is the bounded leaves
        # plus `_full_tok_nbytes` per resident token of full-attention KV.
        it = jnp.dtype(cfg.compute_dtype).itemsize
        n_full = sum(1 for sp in self.lm.plan.all_specs()
                     if full_attn_layer(cfg, sp))
        self._full_tok_nbytes = 2 * cfg.n_kv_heads * cfg.head_dim * it * n_full
        sds, _ = cache_struct(cfg, self.lm.mesh, self.lm.plan, 1, self.max_len)
        self._dense_kv_nbytes = sum(
            int(np.prod(s.shape)) * jnp.dtype(s.dtype).itemsize
            for s in jax.tree.leaves(sds))
        self.free = list(range(self.n_slots))
        self.slot_rid: dict[int, int] = {}
        self.rid_slot: dict[int, int] = {}
        self._prompts: dict[int, tuple] = {}   # live rid → prompt (sharing)
        # device-resident slot state threaded (donated) through the step jit;
        # host mirrors updated from values we already know — no device sync.
        # Per-slot sampling parameters + PRNG base keys live here too, so
        # the fused step samples the whole batch without any host traffic
        # (temp <= 0 rows take the greedy argmax branch).
        self.state = {"pos": jnp.zeros(self.n_slots, jnp.int32),
                      "tok": jnp.zeros(self.n_slots, jnp.int32),
                      "active": jnp.zeros(self.n_slots, bool),
                      "temp": jnp.zeros(self.n_slots, jnp.float32),
                      "top_k": jnp.zeros(self.n_slots, jnp.int32),
                      "top_p": jnp.ones(self.n_slots, jnp.float32),
                      "key": jnp.zeros((self.n_slots, 2), jnp.uint32)}
        n_moe = sum(1 for sp in self.lm.plan.all_specs() if sp.use_moe)
        if n_moe and cfg.moe.n_experts:
            # expert activation counts accumulate device-side too — fetched
            # (and reset) only at placement ticks via take_moe_counts()
            self.state["moe_counts"] = jnp.zeros((n_moe, cfg.moe.n_experts),
                                                 jnp.float32)
        if self.sparsity is not None:
            # online-sparsity window [blocks_scored, blocks_attended,
            # mass_sum, mass_n], layer-summed — accumulates device-side in
            # the step jit, drained only via take_sparsity_stats()
            self.state["sparsity"] = jnp.zeros(4, jnp.float32)
        if self.spec_ctl is not None:
            # speculation window [drafted, accepted, emitted, verifies] —
            # accumulates inside the verify jit, drained only via
            # take_spec_stats(), so host_fetches == steps survives spec
            self.state["spec"] = jnp.zeros(4, jnp.float32)
        self.state = pl.replicate(self.state)
        self.pos_h = np.zeros(self.n_slots, np.int64)      # next write position
        self.tok_h = np.zeros(self.n_slots, np.int64)      # current input token
        self.tokens_h = np.zeros(self.n_slots, np.int64)   # pool-accounted tokens
        self.preempted: list[tuple] = []   # (rid, cache_one, next_tok, pos)
        # pinned out-shardings: the composed cache keeps its arena/private
        # layout and the slot state stays replicated across every donated
        # call — the layout fixed point of the hot loop
        state_sp = pl.slot_state_specs(self.state)
        if self.paged:
            private_sp, merged_sp = pl.paged_cache_specs(
                cfg, self.lm.plan, self.n_slots, self.max_len,
                self.block_size, quant=self.arena.quant)
            self._insert = pl.donate_jit(self._insert_paged_impl,
                                         donate_argnums=(0, 1),
                                         out_specs=(merged_sp, state_sp))
            self._insert_handle = pl.donate_jit(
                self._insert_handle_impl, donate_argnums=(0, 1),
                out_specs=(merged_sp, state_sp))
            self._extract = pl.donate_jit(self._extract_paged_impl)
            step_cache_sp = merged_sp
        else:
            dense_sp = pl.dense_cache_specs(cfg, self.lm.plan, self.n_slots,
                                            self.max_len)
            self._insert = pl.donate_jit(self._insert_impl,
                                         donate_argnums=(0, 1),
                                         out_specs=(dense_sp, state_sp))
            self._extract = pl.donate_jit(self._extract_impl)
            step_cache_sp = dense_sp
        self._step = pl.donate_jit(self._step_impl, donate_argnums=(1, 2),
                                   out_specs=(step_cache_sp, state_sp, P()))
        self._verify = None
        if self.spec_ctl is not None:
            self._verify = pl.donate_jit(
                self._verify_impl, donate_argnums=(1, 2),
                out_specs=(step_cache_sp, state_sp, P()))
        self.greedy_h = np.zeros(self.n_slots, bool)   # slot temp <= 0

    # ---- arena compose/split -----------------------------------------
    # Paged jit calls take (private ∪ arena) and write the donated arena
    # leaves back, so the prefill engine sharing this arena never reads a
    # buffer this engine invalidated (execution is sequential in-process).
    def _full_cache(self):
        if not self.paged:
            return self.cache
        return merge_arena_cache(self.lm.cfg, self.lm.plan, self.cache,
                                 self.arena.kv)

    def _store_cache(self, cache):
        if not self.paged:
            self.cache = cache
            return
        self.cache, self.arena.kv = split_arena_cache(self.lm.cfg,
                                                      self.lm.plan, cache)

    def _true_kv_nbytes(self, n_tokens: int) -> int:
        """REAL bytes of a request's KV payload at `n_tokens` resident
        tokens: bounded leaves (ring KV, mamba state) plus per-token
        full-attention KV — the transfer-cost figure that does NOT meter
        max_len padding (a 64-token prompt in a max_len=2048 cache used to
        charge 32× its real bytes)."""
        bounded = self._dense_kv_nbytes - self._full_tok_nbytes * self.max_len
        return bounded + self._full_tok_nbytes * min(n_tokens, self.max_len)

    # ---- paged layout helpers (trace-level) --------------------------
    def _attn_classes(self):
        """[(spec, (sink, recent)) for period entries], same for rem."""
        cfg = self.lm.cfg
        per = [(s, cache_window(cfg, s)) for s in self.lm.plan.period]
        rem = [(s, cache_window(cfg, s)) for s in self.lm.plan.rem]
        return per, rem

    def _insert_attn_paged(self, win, entry, one, slot, wtbl, stacked):
        """Scatter one request's dense per-layer KV into arena blocks.
        Full layers write through `wtbl` (shared prefix entries redirected to
        the null block — mapped, not copied); ring layers overwrite the
        slot's statically owned block run. Full-layer writes recompute the
        written blocks' key summaries in the same jit, so dense→paged
        (re-)admission never leaves a stale summary (shared prefix entries
        redirect to the null block — the lender's summaries stand)."""
        sink, recent = win
        bs = self.block_size
        if not (sink or recent) and "kscale" in entry:
            return self._insert_attn_quant(entry, one, wtbl, stacked)
        out = dict(entry)
        for name in ("k", "v"):
            a = entry[name]
            o = one[name][:, 0] if stacked else one[name][0]   # [(R,) L, K, h]
            if sink or recent:
                bpw = ring_block_count(sink, recent, bs)
                blocks = dense_kv_to_blocks(o, bpw, bs).astype(a.dtype)
                start = (0, slot * bpw, 0, 0, 0) if stacked else \
                    (slot * bpw, 0, 0, 0)
                a = jax.lax.dynamic_update_slice(a, blocks, start)
            else:
                blocks = dense_kv_to_blocks(o, self.max_blocks,
                                            bs).astype(a.dtype)
                a = a.at[:, wtbl].set(blocks) if stacked else \
                    a.at[wtbl].set(blocks)
            out[name] = a
        if wtbl is not None and "kmin" in entry:
            out["kmin"], out["kmax"], out["kmean"] = \
                attn_mod.update_block_summaries(
                    entry["kmin"], entry["kmax"], entry["kmean"], out["k"],
                    wtbl, layer=(jnp.arange(entry["k"].shape[0])
                                 if stacked else None))
        return out

    def _insert_attn_quant(self, entry, one, wtbl, stacked):
        """Dense-scatter admission into a QUANTIZED full-attention arena.

        Preemption round-trips bit-exactly: an extracted cache carries the
        raw int8 payload + scale-plane sidecar ("kq"/"kscale"/"ktok", v
        likewise) next to its dequantized dense view, and re-admission
        scatters those ints VERBATIM — float requantization is not exactly
        idempotent, the sidecar is. A fresh dense f32 cache (no sidecar)
        takes the per-token provisional quantization — the same pure
        per-token function every write path uses, so a later seal of these
        blocks lands the identical bits a prefill-filled block would.
        Summaries recompute over the DEQUANTIZED content in the same jit
        (zero-stale-scale rides zero-stale-summary); shared-prefix entries
        are already redirected to the null block in `wtbl`, so a lender's
        payload, scales and summaries all stand untouched."""
        out = dict(entry)
        ix = (slice(None), wtbl) if stacked else wtbl
        if "kq" in one:
            for name, qn, sn, tn in (("k", "kq", "kscale", "ktok"),
                                     ("v", "vq", "vscale", "vtok")):
                oq = one[qn][:, 0] if stacked else one[qn][0]
                osc = one[sn][:, 0] if stacked else one[sn][0]
                otk = one[tn][:, 0] if stacked else one[tn][0]
                out[name] = out[name].at[ix].set(oq)
                out[sn] = out[sn].at[ix].set(osc)
                out[tn] = out[tn].at[ix].set(otk)
        else:
            bs = self.block_size
            for name, sn, tn in (("k", "kscale", "ktok"),
                                 ("v", "vscale", "vtok")):
                o = one[name][:, 0] if stacked else one[name][0]
                q, ts = attn_mod.quant_tokens(o)       # [(R,) L, K, h] / [..K]
                blocks = dense_kv_to_blocks(q, self.max_blocks, bs)
                tsb = dense_kv_to_blocks(ts[..., None], self.max_blocks,
                                         bs)[..., 0]   # [(R,) nb, K, bs]
                out[name] = out[name].at[ix].set(blocks)
                out[sn] = out[sn].at[ix].set(0.0)      # all rewritten: unseal
                out[tn] = out[tn].at[ix].set(tsb)
        out["kmin"], out["kmax"], out["kmean"] = \
            attn_mod.update_block_summaries(
                entry["kmin"], entry["kmax"], entry["kmean"], out["k"],
                wtbl, k_scale=out["kscale"], k_tok=out["ktok"],
                layer=jnp.arange(entry["k"].shape[0]) if stacked else None)
        return out

    def _extract_attn_paged(self, win, entry, slot, tbl, stacked):
        """Gather one slot's dense per-layer KV back out of the arenas.

        Quantized arenas return the DEQUANTIZED f32 dense view under the
        usual "k"/"v" names (the interchange format every generic consumer
        reads) plus the raw sidecar leaves ("kq"/"kscale"/"ktok", v
        likewise, in block-major layout) that `_insert_attn_quant` scatters
        back verbatim on re-admission — the int8 payload and its scale
        plane survive a preempt/resume round trip bit-exactly."""
        sink, recent = win
        bs = self.block_size
        quant = "kscale" in entry
        out = {}
        for name in ("k", "v"):
            a = entry[name]
            K, h = a.shape[-3], a.shape[-1]
            if sink or recent:
                W = sink + recent
                bpw = ring_block_count(sink, recent, bs)
                if stacked:
                    blocks = jax.lax.dynamic_slice(
                        a, (0, slot * bpw, 0, 0, 0),
                        (a.shape[0], bpw, K, bs, h))
                else:
                    blocks = jax.lax.dynamic_slice(
                        a, (slot * bpw, 0, 0, 0), (bpw, K, bs, h))
                x = blocks_to_dense_kv(blocks, W)
            else:
                blocks = a[:, tbl] if stacked else a[tbl]
                if quant:
                    sn, tn = ("kscale", "ktok") if name == "k" else \
                        ("vscale", "vtok")
                    sc = entry[sn][:, tbl] if stacked else entry[sn][tbl]
                    tk = entry[tn][:, tbl] if stacked else entry[tn][tbl]
                    for raw, lv in ((blocks, name[0] + "q"), (sc, sn),
                                    (tk, tn)):
                        out[lv] = raw[:, None] if stacked else raw[None]
                    blocks = attn_mod.dequant_pages(blocks, sc, tk)
                x = blocks_to_dense_kv(blocks, self.max_len)
            out[name] = x[:, None] if stacked else x[None]
        return out

    # ---- jit bodies --------------------------------------------------
    def _slot_state(self, state, slots, toks, poss, samp):
        """Write the admitted slots' scalar state + sampling rows."""
        temps, tks, tps, keys = samp
        state = dict(state)
        state.update(pos=state["pos"].at[slots].set(poss),
                     tok=state["tok"].at[slots].set(toks),
                     active=state["active"].at[slots].set(True),
                     temp=state["temp"].at[slots].set(temps),
                     top_k=state["top_k"].at[slots].set(tks),
                     top_p=state["top_p"].at[slots].set(tps),
                     key=state["key"].at[slots].set(keys))
        return state

    def _insert_impl(self, cache_all, state, caches, slots, toks, poss, samp):
        """Admit len(caches) B=1 caches into `slots` in one call."""
        per, rem = cache_all["period"], cache_all["rem"]
        for j in range(len(caches)):
            s = slots[j]
            per = jax.tree.map(lambda a, o, s=s: a.at[:, s].set(o[:, 0]),
                               per, caches[j]["period"])
            rem = jax.tree.map(lambda a, o, s=s: a.at[s].set(o[0]),
                               rem, caches[j]["rem"])
        state = self._slot_state(state, slots, toks, poss, samp)
        return {"period": per, "rem": rem, "pos": cache_all["pos"]}, state

    def _insert_paged_impl(self, cache_all, state, caches, slots, toks, poss,
                           samp, tbls, shns):
        """Paged admission: scatter each B=1 dense cache into arena blocks
        through its table row (tbls [n, max_blocks]); the first shns[j]
        entries are prefix blocks mapped from a lender and must not be
        written (redirected to the null block). Non-attention layer state
        stays per-slot."""
        per_cls, rem_cls = self._attn_classes()
        per = list(cache_all["period"])
        rem = list(cache_all["rem"])
        nb_iota = jnp.arange(self.max_blocks)
        for j in range(len(caches)):
            s = slots[j]
            wtbl = jnp.where(nb_iota < shns[j], 0, tbls[j])
            for i, (spec, win) in enumerate(per_cls):
                one = caches[j]["period"][i]
                if spec.kind == "attn":
                    per[i] = self._insert_attn_paged(win, per[i], one, s,
                                                     wtbl, stacked=True)
                else:
                    per[i] = jax.tree.map(
                        lambda a, o, s=s: a.at[:, s].set(o[:, 0]),
                        per[i], one)
            for i, (spec, win) in enumerate(rem_cls):
                one = caches[j]["rem"][i]
                if spec.kind == "attn":
                    rem[i] = self._insert_attn_paged(win, rem[i], one, s,
                                                     wtbl, stacked=False)
                else:
                    rem[i] = jax.tree.map(
                        lambda a, o, s=s: a.at[s].set(o[0]), rem[i], one)
        state = self._slot_state(state, slots, toks, poss, samp)
        return {"period": tuple(per), "rem": tuple(rem),
                "pos": cache_all["pos"]}, state

    def _insert_handle_impl(self, cache_all, state, privs, slots, toks, poss,
                            samp):
        """Zero-copy (block-handoff) admission: the full-attention KV is
        ALREADY in the arena blocks named by each request's table — only
        the bounded private leaves (ring KV scattered into the slot's
        static ring run, mamba state, scalars) are written. The dense
        scatter of `_insert_paged_impl` survives as the compat path."""
        per_cls, rem_cls = self._attn_classes()
        per = list(cache_all["period"])
        rem = list(cache_all["rem"])
        for j in range(len(privs)):
            s = slots[j]
            for i, (spec, win) in enumerate(per_cls):
                one = privs[j]["period"][i]
                if one is None:
                    continue                    # full-attn: lives in arena
                if spec.kind == "attn":
                    per[i] = self._insert_attn_paged(win, per[i], one, s,
                                                     None, stacked=True)
                else:
                    per[i] = jax.tree.map(
                        lambda a, o, s=s: a.at[:, s].set(o[:, 0]),
                        per[i], one)
            for i, (spec, win) in enumerate(rem_cls):
                one = privs[j]["rem"][i]
                if one is None:
                    continue
                if spec.kind == "attn":
                    rem[i] = self._insert_attn_paged(win, rem[i], one, s,
                                                     None, stacked=False)
                else:
                    rem[i] = jax.tree.map(
                        lambda a, o, s=s: a.at[s].set(o[0]), rem[i], one)
        state = self._slot_state(state, slots, toks, poss, samp)
        return {"period": tuple(per), "rem": tuple(rem),
                "pos": cache_all["pos"]}, state

    def _step_impl(self, params, cache, state, tables, block_tbl):
        new_cache, logits, aux = self.lm.decode(
            params, cache, state["tok"][:, None], state["pos"][:, None],
            tables=tables, token_mask=state["active"], block_tables=block_tbl)
        # fused per-slot sampling: the token following pos sees pos+1 context
        # tokens — folding that into the slot's base key makes the draw a
        # pure function of (seed, position), so preempt/resume and paged vs
        # dense layouts reproduce the same stream. Greedy slots (temp <= 0)
        # reduce to the old argmax bit-exactly.
        nxt = sample_tokens(logits, state["temp"], state["top_k"],
                            state["top_p"], state["key"], state["pos"] + 1)
        act = state["active"]
        new_state = dict(state)
        new_state.update(pos=state["pos"] + act.astype(jnp.int32),
                         tok=jnp.where(act, nxt, state["tok"]))
        if "moe_counts" in state:
            cnts = ([c.reshape(-1, c.shape[-1]) for c in aux["period_counts"]]
                    + [c[None] for c in aux["rem_counts"]])
            new_state["moe_counts"] = (state["moe_counts"] +
                                       jnp.concatenate(cnts, axis=0))
        if "sparsity" in state:
            # per-layer [4] vectors (period entries scan-stacked [n_rep, 4])
            vecs = [a.sum(0) for a in aux.get("period_sparsity", ())] \
                + list(aux.get("rem_sparsity", ()))
            if vecs:
                new_state["sparsity"] = state["sparsity"] + sum(vecs)
        return new_cache, new_state, nxt

    def _verify_impl(self, params, cache, state, tables, block_tbl, drafts,
                     draft_len):
        """Batched speculative verify: feed every slot's window
        [current token, draft_1..draft_k] through a READ-ONLY forward,
        accept the longest prefix matching the model's own greedy argmax,
        and land exactly the accepted rows' K/V with a masked commit —
        rejected draft positions never touch a block or its summary, so
        rollback is the write never happening. Position 0 reproduces the
        baseline step bit-exactly (greedy slots reduce to the same argmax;
        sampled slots draw with the same (key, pos+1) fold), which is what
        makes the emitted greedy stream identical to non-speculative decode
        under ANY draft source. → (cache, state, packed [B, k+2]) where
        packed[:, :k+1] are the emitted tokens and packed[:, -1] the
        per-slot emit count — ONE host fetch for the whole window."""
        B, k = drafts.shape
        act = state["active"]
        toks = jnp.concatenate([state["tok"][:, None], drafts], axis=1)
        logits, staged, aux = self.lm.verify(
            params, cache, toks, state["pos"], tables=tables,
            token_mask=act, block_tables=block_tbl)
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        nxt0 = sample_tokens(logits[:, 0], state["temp"], state["top_k"],
                             state["top_p"], state["key"], state["pos"] + 1)
        is_greedy = state["temp"] <= 0.0
        dmask = jnp.arange(k)[None, :] < draft_len[:, None]
        match = (drafts == greedy[:, :k]) & dmask & is_greedy[:, None]
        # accepted prefix length: draft_i is right iff it equals the greedy
        # continuation given positions < t+i — all of which were themselves
        # accepted (cumprod), exactly the sequential decode induction
        a = jnp.cumprod(match.astype(jnp.int32), axis=1).sum(axis=1)
        n_emit = jnp.where(act, a + 1, 0)
        emit = jnp.concatenate([nxt0[:, None], greedy[:, 1:]], axis=1)
        new_tok = jnp.where(act, emit[jnp.arange(B), a], state["tok"])
        new_cache = self.lm.verify_commit(staged, state["pos"], n_emit,
                                          block_tbl)
        new_state = dict(state)
        new_state.update(pos=state["pos"] + n_emit, tok=new_tok)
        if "moe_counts" in state:
            cnts = ([c.reshape(-1, c.shape[-1]) for c in aux["period_counts"]]
                    + [c[None] for c in aux["rem_counts"]])
            new_state["moe_counts"] = (state["moe_counts"] +
                                       jnp.concatenate(cnts, axis=0))
        actf = act.astype(jnp.float32)
        new_state["spec"] = state["spec"] + jnp.stack(
            [(actf * draft_len).sum(), (actf * a).sum(),
             n_emit.sum().astype(jnp.float32), jnp.ones((), jnp.float32)])
        packed = jnp.concatenate([emit, n_emit[:, None]], axis=1)
        return new_cache, new_state, packed

    def _extract_impl(self, cache_all, slot):
        """Pull one slot back out as a B=1 cache (preemption path)."""
        per = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1),
            cache_all["period"])
        rem = jax.tree.map(
            lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0),
            cache_all["rem"])
        return {"period": per, "rem": rem, "pos": cache_all["pos"]}

    def _extract_paged_impl(self, cache_all, slot, tbl):
        """Pull one slot's KV out of the arenas as a dense B=1 cache
        (preemption / re-admission interchange format)."""
        per_cls, rem_cls = self._attn_classes()
        per, rem = [], []
        for i, (spec, win) in enumerate(per_cls):
            e = cache_all["period"][i]
            if spec.kind == "attn":
                per.append(self._extract_attn_paged(win, e, slot, tbl,
                                                    stacked=True))
            else:
                per.append(jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=1),
                    e))
        for i, (spec, win) in enumerate(rem_cls):
            e = cache_all["rem"][i]
            if spec.kind == "attn":
                rem.append(self._extract_attn_paged(win, e, slot, tbl,
                                                    stacked=False))
            else:
                rem.append(jax.tree.map(
                    lambda a: jax.lax.dynamic_slice_in_dim(a, slot, 1, axis=0),
                    e))
        return {"period": tuple(per), "rem": tuple(rem),
                "pos": cache_all["pos"]}

    # ------------------------------------------------------------------
    def _refresh_tables(self):
        """Device block-table refresh, with the resident-block count fed to
        the step jit pow2-BUCKETED (lo=8 floor, the prefill chunk-bucket
        convention): the jit traces once per bucket instead of once per
        block-boundary crossing as contexts grow, and short-context steps
        hand the kernels a narrow table — the paged_decode grid (and its
        per-block DMAs) scales with the bucket, not max_len. Every live
        slot's resident blocks fit the bucket by construction; stale rows
        of freed slots are clamped to the null block by the write guard."""
        cur = 1
        for slot in self.slot_rid:
            cur = max(cur, self.pool.blocks_for(int(self.tokens_h[slot])))
        nb = min(_bucket(cur, lo=8), self.max_blocks)
        if self._tbl_dirty or nb != self._tbl_bucket:
            self._tbl_dev = jnp.asarray(self.tables_h[:, :nb])
            self._tbl_bucket = nb
            self._tbl_dirty = False

    def take_sparsity_stats(self):
        """Fetch + reset the device-side online-sparsity window and fold it
        into stats (blocks_scored / blocks_attended / attn_mass_*, layer-
        averaged — see serving/sparsity.py). → the layer-averaged [4] np
        vector, or None when online sparsity is off. The only host sync for
        these counters — call at monitor ticks / run end, not per step."""
        v = drain_accumulator(self.state, "sparsity")
        if v is None:
            return None
        self.sparsity.note(self.stats, v)
        L = max(self.sparsity.plan.n_sparse_layers, 1)
        return v / L

    def take_spec_stats(self):
        """Fetch + reset the device-side speculation window ([drafted,
        accepted, emitted, verify steps], see SpecController.stats_keys)
        and fold it into stats. → the raw [4] np vector, or None when
        speculation is off. The only host sync for the spec counters —
        call at monitor ticks / run end, not per step."""
        v = drain_accumulator(self.state, "spec")
        if v is None:
            return None
        SpecController.note(self.stats, v)
        return v

    def has_capacity(self) -> bool:
        return len(self.free) > 0

    def _find_shared(self, prompt, cached: int) -> list[int]:
        """Physical prefix blocks to map for an admission whose first
        `cached` tokens are radix-cached: a live request whose prompt shares
        that prefix lends its FULL prefix blocks (floor — the partial tail
        block is always privately copied by the borrower). Returns [] when
        no lender is resident (the credit is then not taken: PR 1 credited
        blocks that were not physically anywhere)."""
        shn = self.pool.shareable_blocks(cached)
        if shn <= 0 or prompt is None:
            return []
        prompt = tuple(prompt)
        for rid, ptoks in self._prompts.items():
            if (ptoks is not None and len(ptoks) >= cached
                    and tuple(ptoks[:cached]) == prompt[:cached]):
                blocks = self.pool.owned(rid)
                if len(blocks) >= shn:
                    return blocks[:shn]
        return []

    def _admit_handle(self, rid: int, hb: BlockHandoff, pos: int) -> bool:
        """Zero-copy admission: rename the handoff's pool ownership to the
        decode rid, extend capacity for the next token, and point the
        slot's table row at the (already written) blocks. Fails clean —
        ownership is handed back so the server can requeue the handle."""
        self.pool.transfer(hb.key, rid)
        grown = self.pool.extend(rid, pos, pos + 1)
        if grown is None:
            self.arena.reclaim(1)
            grown = self.pool.extend(rid, pos, pos + 1)
        if grown is None:
            self.pool.transfer(rid, hb.key)
            return False
        self.stats["blocks_fresh"] += len(grown)
        return True

    def admit_batch(self, items: list[tuple]) -> dict[int, bool]:
        """items: (rid, cache_one, next_token, pos, cached_tokens[, prompt
        [, sampling_params]]). `cache_one` is either a B=1 dense cache (the
        scatter compat path, also used for preemption re-admission) or a
        `BlockHandoff` (paged prefill: ownership of the already-written
        arena blocks transfers to the decode rid — zero KV copy). Inserts
        every admissible item in ONE donated jit call per kind;
        → {rid: admitted}. With paged KV and a dense cache, `prompt`
        enables prefix-sharing admission: full blocks of the cached prefix
        are mapped from a live lender instead of copied. `sampling_params`
        (SamplingParams, None → greedy) lands in the slot's device-side
        parameter tensors."""
        out: dict[int, bool] = {}
        batch, hbatch = [], []
        for item in items:
            rid, cache_one, tok, pos, cached = item[:5]
            prompt = item[5] if len(item) > 5 else None
            sparams = item[6] if len(item) > 6 else None
            handoff = isinstance(cache_one, BlockHandoff)
            if not self.free:
                out[rid] = False
                continue
            if handoff:
                if not self.paged:
                    raise ValueError("BlockHandoff admission needs paged KV")
                if not self._admit_handle(rid, cache_one, pos):
                    out[rid] = False
                    continue
                slot = self.free.pop()
                tbl = self.pool.owned(rid)
                row = np.zeros(self.max_blocks, np.int32)
                row[:len(tbl)] = tbl
                self.tables_h[slot] = row
                shn = 0
            elif self.paged:
                shared = self._find_shared(prompt, cached)
                tbl = self.pool.allocate(rid, pos + 1, shared=shared)
                if tbl is None:
                    self.arena.reclaim(self.pool.blocks_for(pos + 1)
                                       - len(shared))
                    tbl = self.pool.allocate(rid, pos + 1, shared=shared)
                if tbl is None:
                    out[rid] = False
                    continue
                self.stats["blocks_shared"] += len(shared)
                self.stats["blocks_fresh"] += len(tbl) - len(shared)
                slot = self.free.pop()
                row = np.zeros(self.max_blocks, np.int32)
                row[:len(tbl)] = tbl
                self.tables_h[slot] = row
                shn = len(shared)
            else:
                if self.pool.allocate(rid, pos + 1,
                                      cached_tokens=cached) is None:
                    out[rid] = False
                    continue
                slot = self.free.pop()
                row, shn = None, 0
            self.slot_rid[slot] = rid
            self.rid_slot[rid] = slot
            self._prompts[rid] = tuple(prompt) if prompt is not None else None
            self.pos_h[slot] = pos
            self.tok_h[slot] = tok
            self.tokens_h[slot] = pos + 1
            # transfer-cost model: TRUE payload bytes (resident tokens, not
            # the max_len allocation) next to the padded figure the old
            # meter charged; handoff_copy_bytes is the full-attention KV
            # physically copied at admission — 0 on the zero-copy path, the
            # whole max_len scatter on the dense compat path
            self.stats["kv_transfer_bytes"] += self._true_kv_nbytes(pos)
            self.stats["kv_transfer_bytes_padded"] += self._dense_kv_nbytes
            if not handoff:
                self.stats["handoff_copy_bytes"] += \
                    self._full_tok_nbytes * self.max_len
            self.stats["admits"] += 1
            drow = device_row(sparams, rid)
            rec = (slot, cache_one.private if handoff else cache_one, tok,
                   pos, row, shn, drow)
            (hbatch if handoff else batch).append(rec)
            # host mirror of the greedy predicate: the draft gather skips
            # sampled slots without touching device state
            self.greedy_h[slot] = float(drow[0]) <= 0.0
            if self.spec_ctl is not None:
                self.spec_ctl.on_admit(rid, prompt, tok)
            out[rid] = True

        # pad to a pow2 batch by repeating the last insert (idempotent:
        # same slot, same values) — bounds jit retraces to log2(n_slots)
        def _prep(b):
            while len(b) & (len(b) - 1):
                b.append(b[-1])
            slots = jnp.asarray([x[0] for x in b], jnp.int32)
            toks = jnp.asarray([x[2] for x in b], jnp.int32)
            poss = jnp.asarray([x[3] for x in b], jnp.int32)
            caches = tuple(x[1] for x in b)
            samp = (jnp.asarray([x[6][0] for x in b], jnp.float32),
                    jnp.asarray([x[6][1] for x in b], jnp.int32),
                    jnp.asarray([x[6][2] for x in b], jnp.float32),
                    jnp.asarray(np.stack([x[6][3] for x in b])))
            return slots, toks, poss, caches, samp

        if batch:
            slots, toks, poss, caches, samp = _prep(batch)
            if self.paged:
                tbls = jnp.asarray(np.stack([b[4] for b in batch]), jnp.int32)
                shns = jnp.asarray([b[5] for b in batch], jnp.int32)
                cache, self.state = self._insert(
                    self._full_cache(), self.state, caches, slots, toks,
                    poss, samp, tbls, shns)
                self._store_cache(cache)
            else:
                self.cache, self.state = self._insert(
                    self.cache, self.state, caches, slots, toks, poss, samp)
        if hbatch:
            slots, toks, poss, privs, samp = _prep(hbatch)
            cache, self.state = self._insert_handle(
                self._full_cache(), self.state, privs, slots, toks, poss,
                samp)
            self._store_cache(cache)
        if self.paged and (batch or hbatch):
            self._tbl_dirty = True       # next step() re-buckets + uploads
        return out

    def admit(self, rid: int, cache_one, first_token: int, prompt_len: int,
              cached_tokens: int = 0, prompt: Optional[tuple] = None,
              params: Optional[SamplingParams] = None) -> bool:
        return self.admit_batch([(rid, cache_one, first_token, prompt_len,
                                  cached_tokens, prompt, params)])[rid]

    # ------------------------------------------------------------------
    def step(self):
        """One batched decode step. Without speculation: {rid: next_token}
        for active slots (unchanged contract). With speculation enabled
        ({rid: [tokens]}, ≥ 1 each): draft up to k candidates per greedy
        slot and run the batched verify window instead of the single-token
        step — still exactly one device→host fetch. Requests whose block
        allocation cannot grow are preempted into self.preempted (cache
        extracted for later re-admission)."""
        if not self.slot_rid:
            return {}
        if self.spec_ctl is None:
            return self._step_base()
        drafts_h, dlen_h = self._gather_drafts()
        if not dlen_h.any():
            # nothing to speculate this step: ride the plain single-token
            # jit (cheaper than a k+1 window of guaranteed-empty drafts)
            out = {rid: [t] for rid, t in self._step_base().items()}
            for rid, ts in out.items():
                self.spec_ctl.on_tokens(rid, ts)
            return out
        return self._step_spec(drafts_h, dlen_h)

    def _gather_drafts(self):
        """Host-side draft gather → (drafts [n_slots, k] i32, dlen
        [n_slots] i32). Sampled slots and slots at the max_len capacity
        wall are skipped (their row rides the verify window as a plain
        single-token step); draft length is clamped so every candidate
        write position stays below max_len."""
        k = self.spec_ctl.k
        drafts = np.zeros((self.n_slots, k), np.int32)
        dlen = np.zeros(self.n_slots, np.int32)
        for slot, rid in self.slot_rid.items():
            if not self.greedy_h[slot]:
                continue
            room = self.max_len - int(self.tokens_h[slot])
            if room <= 0:
                continue
            d = self.spec_ctl.draft(rid)[:room]
            if not d:
                continue
            drafts[slot, :len(d)] = d
            dlen[slot] = len(d)
        return drafts, dlen

    def _step_spec(self, drafts_h, dlen_h):
        """One speculative verify step → {rid: [tokens]}."""
        t0 = time.monotonic()
        # pre-extend each drafting slot's allocation to cover its window's
        # write positions; a slot that cannot grow (even after reclaim)
        # degrades to a plain single-token row — never preempt here, the
        # baseline row still fits the blocks it already owns
        touched = 0
        for slot, rid in self.slot_rid.items():
            cur = int(self.tokens_h[slot])
            touched += self.pool.blocks_for(cur)
            d = int(dlen_h[slot])
            want = min(cur + d, self.max_len)
            if d <= 0 or want <= cur:
                continue
            nb_used = self.pool.blocks_for(cur)
            grown = self.pool.extend(rid, cur, want)
            if grown is None and self.arena.reclaim(
                    max(self.pool.blocks_for(want) - nb_used, 1)):
                grown = self.pool.extend(rid, cur, want)
            if grown is None:
                drafts_h[slot] = 0
                dlen_h[slot] = 0
                continue
            for b in grown:
                self.tables_h[slot, nb_used] = b
                nb_used += 1
            if grown:
                self._tbl_dirty = True
                self.stats["blocks_fresh"] += len(grown)
            self.tokens_h[slot] = want
        self.stats["blocks_touched"] += touched
        with span("omni.decode.dispatch"):
            self._refresh_tables()
            cache, self.state, packed = self._verify(
                self.params, self._full_cache(), self.state, self.tables,
                self._tbl_dev, jnp.asarray(drafts_h), jnp.asarray(dlen_h))
            self._store_cache(cache)
        with span("omni.decode.fetch"):
            packed_np = np.asarray(packed)  # the single per-step host fetch
        self.stats["host_fetches"] += 1
        out = {}
        ntok = 0
        for slot, rid in list(self.slot_rid.items()):
            n = int(packed_np[slot, -1])
            toks = [int(t) for t in packed_np[slot, :n]]
            out[rid] = toks
            ntok += n
            self.pos_h[slot] += n
            if n:
                self.tok_h[slot] = toks[-1]
            covered = int(self.tokens_h[slot])
            new_tokens = min(int(self.pos_h[slot]) + 1, self.max_len)
            if new_tokens > covered:
                # full accept: the next input token needs one position past
                # the pre-extended window — same grow path as the baseline
                nb_used = self.pool.blocks_for(covered)
                grown = self.pool.extend(rid, covered, new_tokens)
                if grown is None and self.arena.reclaim(1):
                    grown = self.pool.extend(rid, covered, new_tokens)
                if grown is None:
                    self.stats["preemptions"] += 1
                    self.preempted.append(self._preempt(rid))
                    continue
                for b in grown:
                    self.tables_h[slot, nb_used] = b
                    nb_used += 1
                if grown:
                    self._tbl_dirty = True
                    self.stats["blocks_fresh"] += len(grown)
            elif new_tokens < covered:
                # rejected tail: hand the over-extended blocks back and
                # zero their table entries. The masked commit never wrote
                # them (rejected rows land in the null block), so the
                # released blocks carry no new content and no summary goes
                # stale — this IS the rollback.
                dropped = self.pool.shrink(rid, covered, new_tokens)
                if dropped:
                    nb_new = self.pool.blocks_for(new_tokens)
                    self.tables_h[slot, nb_new:nb_new + len(dropped)] = 0
                    self._tbl_dirty = True
            self.tokens_h[slot] = new_tokens
            self.spec_ctl.on_tokens(rid, toks)
        dt = time.monotonic() - t0
        self.stats["steps"] += 1
        self.stats["tokens"] += ntok
        self.stats["busy_s"] += dt
        return out

    def _step_base(self) -> dict[int, int]:
        """The non-speculative single-token step → {rid: next_token}."""
        t0 = time.monotonic()
        with span("omni.decode.dispatch"):
            if self.paged:
                self._refresh_tables()
            cache, self.state, nxt = self._step(
                self.params, self._full_cache(), self.state, self.tables,
                self._tbl_dev if self.paged else None)
            self._store_cache(cache)
        with span("omni.decode.fetch"):
            next_np = np.asarray(nxt)      # the single per-step host fetch
        self.stats["host_fetches"] += 1
        out = {}
        for slot, rid in list(self.slot_rid.items()):
            tok = int(next_np[slot])
            out[rid] = tok
            self.pos_h[slot] += 1
            self.tok_h[slot] = tok
            # work-based read metric: full-attention blocks gathered for this
            # slot this step (the dense layout always touches max_blocks)
            self.stats["blocks_touched"] += (
                self.pool.blocks_for(int(self.tokens_h[slot]))
                if self.paged else self.max_blocks)
            # capacity is capped at max_len: a request decoding past it keeps
            # emitting (its writes are dropped — null block for paged, OOB
            # scatter drop for dense) but never grows its allocation —
            # growing would index past the table row
            cur = int(self.tokens_h[slot])
            new_tokens = min(cur + 1, self.max_len)
            nb_used = self.pool.blocks_for(cur)
            grown = self.pool.extend(rid, cur, new_tokens)
            if grown is None and self.paged:
                # before preempting, reclaim shared cache state (LRU prefix
                # store entries) — evicting a snapshot is always cheaper
                # than extracting and re-prefilling a live request
                if self.arena.reclaim(1):
                    grown = self.pool.extend(rid, cur, new_tokens)
            if grown is None:
                # the sampled token is already in `out` (delivered once); the
                # preemption record carries it as the resume input so it is
                # neither dropped nor replayed on re-admission
                self.stats["preemptions"] += 1
                self.preempted.append(self._preempt(rid))
                continue
            if grown and self.paged:
                for b in grown:
                    self.tables_h[slot, nb_used] = b
                    nb_used += 1
                self._tbl_dirty = True
                self.stats["blocks_fresh"] += len(grown)
            self.tokens_h[slot] = new_tokens
        dt = time.monotonic() - t0
        self.stats["steps"] += 1
        self.stats["tokens"] += len(out)
        self.stats["busy_s"] += dt
        return out

    def take_moe_counts(self):
        """Fetch + reset the device-side expert activation window ([L_moe, E]
        np array, or None for non-MoE models). The only host sync for counts
        — call it at monitor ticks, not per step."""
        return drain_accumulator(self.state, "moe_counts")

    def _preempt(self, rid: int) -> tuple:
        slot = self.rid_slot[rid]
        if self.paged:
            cache_one = self._extract(self._full_cache(), jnp.int32(slot),
                                      jnp.asarray(self.tables_h[slot]))
        else:
            cache_one = self._extract(self.cache, jnp.int32(slot))
        rec = (rid, cache_one, int(self.tok_h[slot]), int(self.pos_h[slot]))
        self._free_slot(rid, slot)
        return rec

    def _free_slot(self, rid: int, slot: int):
        del self.slot_rid[slot]
        del self.rid_slot[rid]
        self._prompts.pop(rid, None)
        if self.spec_ctl is not None:
            self.spec_ctl.on_release(rid)
        self.state["active"] = self.state["active"].at[slot].set(False)
        # a stale temp > 0 on a freed slot would permanently defeat the
        # all-greedy fast path in sample_tokens (jnp.all over every slot)
        self.state["temp"] = self.state["temp"].at[slot].set(0.0)
        self.free.append(slot)
        self.pool.release(rid)
        if self.paged:
            # the freed slot keeps decoding garbage until reused: its writes
            # must land in the null block, not in blocks the pool may hand to
            # another request
            self.tables_h[slot] = 0
            self._tbl_dirty = True

    def release(self, rid: int):
        slot = self.rid_slot.get(rid)
        if slot is not None:
            self._free_slot(rid, slot)
