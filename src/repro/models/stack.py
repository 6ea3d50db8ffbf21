"""Layer-stack engine: periodized scan over heterogeneous layers.

The per-layer spec sequence (attention/mamba × windowed × MoE × compressed) is
decomposed into (period, n_repeats, remainder) — see ModelConfig.periodize —
so compile time is O(period + remainder) while the stack scans over repeats.

Params pytree:
  {'period': (pos0_params, pos1_params, ...),   # leaves stacked [n_rep, ...]
   'rem':    (params, ...)}                      # unstacked
Caches mirror the same structure plus a scalar position.
"""
from __future__ import annotations

import math
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import LayerSpec, ModelConfig
from repro.distributed.ctx import MeshCtx
from repro.models import attention as attn_mod
from repro.models import moe as moe_mod
from repro.models import ssd as ssd_mod
from repro.models.common import ParamDef, rms_norm, stack_defs


# ======================================================================
@dataclass(frozen=True)
class StackPlan:
    period: tuple[LayerSpec, ...]
    n_rep: int
    rem: tuple[LayerSpec, ...]

    @staticmethod
    def from_config(cfg: ModelConfig, pattern: Optional[list[int]] = None) -> "StackPlan":
        if pattern is None:
            pattern = cfg.default_compression_pattern()
        specs = cfg.layer_specs(pattern)
        period, n_rep, rem = cfg.periodize(specs)
        return StackPlan(tuple(period), n_rep, tuple(rem))

    @property
    def n_layers(self) -> int:
        return len(self.period) * self.n_rep + len(self.rem)

    def all_specs(self) -> list[LayerSpec]:
        return list(self.period) * self.n_rep + list(self.rem)


def cache_window(cfg: ModelConfig, spec: LayerSpec) -> tuple[int, int]:
    """(sink, recent) for this layer's KV cache; (0,0) → full cache."""
    if spec.kind != "attn":
        return (0, 0)
    if spec.compressed:
        return (cfg.omniattn.sink_tokens, cfg.omniattn.recent_tokens)
    if spec.window > 0:
        return (0, spec.window)
    return (0, 0)


# ======================================================================
# Parameter schemas
def _fs(cfg):      # FSDP axis for the "replicated big" param dim
    return "data" if cfg.fsdp else None


def attn_defs(cfg: ModelConfig) -> dict:
    D, H, K, h = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    fs, dt = _fs(cfg), cfg.param_dtype
    d = {
        "ln_attn": ParamDef((D,), P(None), dtype=dt, ones=True),
        "wq": ParamDef((D, H * h), P(fs, "model"), dtype=dt),
        "wk": ParamDef((D, K * h), P(fs, "model"), dtype=dt),
        "wv": ParamDef((D, K * h), P(fs, "model"), dtype=dt),
        "wo": ParamDef((H * h, D), P("model", fs), dtype=dt),
    }
    if cfg.qkv_bias:
        d["bq"] = ParamDef((H * h,), P("model"), 0.0, dtype=dt)
        d["bk"] = ParamDef((K * h,), P("model"), 0.0, dtype=dt)
        d["bv"] = ParamDef((K * h,), P("model"), 0.0, dtype=dt)
    if cfg.qk_norm:
        d["q_norm"] = ParamDef((h,), P(None), dtype=dt, ones=True)
        d["k_norm"] = ParamDef((h,), P(None), dtype=dt, ones=True)
    return d


def mamba_defs(cfg: ModelConfig) -> dict:
    D, ssm = cfg.d_model, cfg.ssm
    d_in = ssm.expand * D
    nh = d_in // ssm.head_dim
    N, cw = ssm.d_state, ssm.conv_width
    fs, dt = _fs(cfg), cfg.param_dtype
    return {
        "ln_attn": ParamDef((D,), P(None), dtype=dt, ones=True),
        "w_z": ParamDef((D, d_in), P(fs, "model"), dtype=dt),
        "w_x": ParamDef((D, d_in), P(fs, "model"), dtype=dt),
        "w_bc": ParamDef((D, 2 * N), P(fs, None), dtype=dt),
        "w_dt": ParamDef((D, nh), P(fs, "model"), dtype=dt),
        "dt_bias": ParamDef((nh,), P("model"), 0.0, dtype=dt),
        "conv_x": ParamDef((cw, d_in), P(None, "model"), dtype=dt),
        "conv_bc": ParamDef((cw, 2 * N), P(None, None), dtype=dt),
        "A_log": ParamDef((nh,), P("model"), dtype=dt, ones=True),
        "D_skip": ParamDef((nh,), P("model"), dtype=dt, ones=True),
        "ssm_norm": ParamDef((d_in,), P("model"), dtype=dt, ones=True),
        "out_proj": ParamDef((d_in, D), P("model", fs), dtype=dt),
    }


def ffn_defs(cfg: ModelConfig) -> dict:
    D, F = cfg.d_model, cfg.d_ff
    fs, dt = _fs(cfg), cfg.param_dtype
    return {
        "ln_mlp": ParamDef((D,), P(None), dtype=dt, ones=True),
        "w1": ParamDef((D, F), P(fs, "model"), dtype=dt),
        "w3": ParamDef((D, F), P(fs, "model"), dtype=dt),
        "w2": ParamDef((F, D), P("model", fs), dtype=dt),
    }


def moe_defs(cfg: ModelConfig, mesh: MeshCtx) -> dict:
    D, m = cfg.d_model, cfg.moe
    ep = mesh.ep
    s = moe_mod.default_slot_count(cfg, ep)
    dt = cfg.param_dtype
    d = {
        "ln_mlp": ParamDef((D,), P(None), dtype=dt, ones=True),
        "router": ParamDef((D, m.n_experts), P(None, None), dtype="float32"),
        "moe_w1": ParamDef((ep, s, D, m.d_ff_expert), P("data", None, None, "model"), dtype=dt),
        "moe_w3": ParamDef((ep, s, D, m.d_ff_expert), P("data", None, None, "model"), dtype=dt),
        "moe_w2": ParamDef((ep, s, m.d_ff_expert, D), P("data", None, "model", None), dtype=dt),
    }
    if m.n_shared_experts:
        Fsh = m.n_shared_experts * m.d_ff_expert
        d["shared_w1"] = ParamDef((D, Fsh), P(_fs(cfg), "model"), dtype=dt)
        d["shared_w3"] = ParamDef((D, Fsh), P(_fs(cfg), "model"), dtype=dt)
        d["shared_w2"] = ParamDef((Fsh, D), P("model", _fs(cfg)), dtype=dt)
    return d


def layer_defs(cfg: ModelConfig, mesh: MeshCtx, spec: LayerSpec) -> dict:
    d = attn_defs(cfg) if spec.kind == "attn" else mamba_defs(cfg)
    if spec.use_moe:
        d.update(moe_defs(cfg, mesh))
    elif cfg.d_ff > 0:
        d.update(ffn_defs(cfg))
    return d


def stack_param_defs(cfg: ModelConfig, mesh: MeshCtx, plan: StackPlan) -> dict:
    period = tuple(stack_defs(layer_defs(cfg, mesh, s), plan.n_rep) for s in plan.period)
    rem = tuple(layer_defs(cfg, mesh, s) for s in plan.rem)
    return {"period": period, "rem": rem}


# ======================================================================
# Cache schemas (ShapeDtypeStruct + PartitionSpec builders for the dry-run
# and for real allocation in the serving engine).
def layer_cache_shape(cfg: ModelConfig, mesh: MeshCtx, spec: LayerSpec, B: int,
                      max_len: int) -> dict:
    """Returns {name: (shape, spec)} for one layer's decode cache."""
    bp = mesh.batch_part(B)
    if spec.kind == "attn":
        sink, recent = cache_window(cfg, spec)
        W = (sink + recent) if (sink or recent) else max_len
        K, h = cfg.n_kv_heads, cfg.head_dim
        strat = attn_mod.decode_strategy(K, mesh.tp)
        w_part = mesh.part_if("model", W) if strat == "wseq" else None
        kv_part = "model" if strat == "kv" else None
        sp = P(bp, w_part, kv_part, None)
        return {"k": ((B, W, K, h), sp), "v": ((B, W, K, h), sp)}
    ssm = cfg.ssm
    d_in = ssm.expand * cfg.d_model
    nh = d_in // ssm.head_dim
    return {
        "state": ((B, nh, ssm.head_dim, ssm.d_state),
                  P(bp, mesh.part_if("model", nh), None, None)),
        "conv_x": ((B, ssm.conv_width - 1, d_in),
                   P(bp, None, mesh.part_if("model", d_in))),
        "conv_bc": ((B, ssm.conv_width - 1, 2 * ssm.d_state), P(bp, None, None)),
    }


def cache_struct(cfg: ModelConfig, mesh: MeshCtx, plan: StackPlan, B: int,
                 max_len: int, dtype=None):
    """(ShapeDtypeStruct pytree, PartitionSpec pytree) for the full cache."""
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    def one(spec: LayerSpec, stacked: bool):
        shapes = layer_cache_shape(cfg, mesh, spec, B, max_len)
        sds, sps = {}, {}
        for name, (shp, sp) in shapes.items():
            dt = jnp.float32 if name == "state" else dtype
            if stacked:
                shp = (plan.n_rep,) + shp
                sp = P(*((None,) + tuple(sp)))
            sds[name] = jax.ShapeDtypeStruct(shp, dt)
            sps[name] = sp
        return sds, sps
    period = [one(s, True) for s in plan.period]
    rem = [one(s, False) for s in plan.rem]
    sds = {"period": tuple(p[0] for p in period), "rem": tuple(r[0] for r in rem),
           "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    sps = {"period": tuple(p[1] for p in period), "rem": tuple(r[1] for r in rem),
           "pos": P()}
    return sds, sps


def _alloc_placed(mesh, sds, sps):
    """zeros for every ShapeDtypeStruct leaf, laid out on the mesh per its
    PartitionSpec. The zeros are born sharded (one jit with out_shardings):
    no leaf is staged whole on one device first. On one device the pin is
    dropped, as DevicePlacement.donate_jit drops it, so the zeros stay
    uncommitted like every other single-device serving array (a committed
    input would re-key the unpinned hot jits after their first call)."""
    kw = {} if mesh.n_devices == 1 else \
        {"out_shardings": mesh.tree_shardings(sps)}
    return jax.jit(lambda: jax.tree.map(
        lambda s: jnp.zeros(s.shape, s.dtype), sds), **kw)()


def alloc_cache(cfg, mesh, plan, B, max_len, dtype=None):
    sds, sps = cache_struct(cfg, mesh, plan, B, max_len, dtype)
    return _alloc_placed(mesh, sds, sps)


# ----------------------------------------------------------------------
# Physically paged decode caches: attention KV lives in a global per-layer
# block arena [N, K, block_size, h] indexed through per-slot block tables.
def ring_block_count(sink: int, recent: int, block_size: int) -> int:
    """Blocks backing one slot's sink+recent ring (ceil, last may be partial)."""
    return -(-(sink + recent) // block_size)


def layer_cache_shape_paged(cfg: ModelConfig, mesh: MeshCtx, spec: LayerSpec,
                            n_slots: int, max_len: int, n_arena_blocks: int,
                            block_size: int) -> dict:
    """{name: (shape, spec)} for one layer's paged decode cache.

    Full-attention layers share the pool-managed arena (`n_arena_blocks`
    includes the reserved null block 0); ring layers (windowed / sink+recent
    compressed) have fixed per-slot capacity, so each slot statically owns a
    contiguous run of ring blocks. Non-attention layers keep their per-slot
    dense state (it does not grow with context).
    """
    if spec.kind != "attn":
        return layer_cache_shape(cfg, mesh, spec, n_slots, max_len)
    sink, recent = cache_window(cfg, spec)
    K, h = cfg.n_kv_heads, cfg.head_dim
    if sink or recent:
        N = n_slots * ring_block_count(sink, recent, block_size)
    else:
        N = n_arena_blocks
    sp = P(None, attn_mod.arena_kv_part(K, mesh.tp), None, None)
    return {"k": ((N, K, block_size, h), sp),
            "v": ((N, K, block_size, h), sp)}


def paged_cache_struct(cfg: ModelConfig, mesh: MeshCtx, plan: StackPlan,
                       n_slots: int, max_len: int, n_arena_blocks: int,
                       block_size: int, dtype=None):
    """(ShapeDtypeStruct pytree, PartitionSpec pytree) for the paged cache."""
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    def one(spec: LayerSpec, stacked: bool):
        shapes = layer_cache_shape_paged(cfg, mesh, spec, n_slots, max_len,
                                         n_arena_blocks, block_size)
        sds, sps = {}, {}
        for name, (shp, sp) in shapes.items():
            dt = jnp.float32 if name == "state" else dtype
            if stacked:
                shp = (plan.n_rep,) + shp
                sp = P(*((None,) + tuple(sp)))
            sds[name] = jax.ShapeDtypeStruct(shp, dt)
            sps[name] = sp
        return sds, sps
    period = [one(s, True) for s in plan.period]
    rem = [one(s, False) for s in plan.rem]
    sds = {"period": tuple(p[0] for p in period), "rem": tuple(r[0] for r in rem),
           "pos": jax.ShapeDtypeStruct((), jnp.int32)}
    sps = {"period": tuple(p[1] for p in period), "rem": tuple(r[1] for r in rem),
           "pos": P()}
    return sds, sps


def alloc_paged_cache(cfg, mesh, plan, n_slots, max_len, n_arena_blocks,
                      block_size, dtype=None):
    sds, sps = paged_cache_struct(cfg, mesh, plan, n_slots, max_len,
                                  n_arena_blocks, block_size, dtype)
    return _alloc_placed(mesh, sds, sps)


# ----------------------------------------------------------------------
# Arena/private cache split: with paged *prefill*, the pool-managed
# full-attention block arenas are SHARED between the prefill and decode
# engines (zero-copy admission is a block-table transfer), while everything
# bounded — ring KV, mamba state, per-slot scalars — stays engine-private.
# A cache pytree handed to a jit is composed as (private ∪ arena) and split
# back after the (donated) call; positions with no entry hold None.
def full_attn_layer(cfg: ModelConfig, spec: LayerSpec) -> bool:
    """True for attention layers whose KV grows with context (full cache:
    no ring) — exactly the layers whose KV lives in pool-backed arenas."""
    return spec.kind == "attn" and cache_window(cfg, spec) == (0, 0)


def _drop_entries(cfg, plan, tree, drop_full: bool):
    """None out period/rem entries on one side of the full-attn split."""
    per = tuple(None if full_attn_layer(cfg, s) == drop_full else
                tree["period"][i] for i, s in enumerate(plan.period))
    rem = tuple(None if full_attn_layer(cfg, s) == drop_full else
                tree["rem"][i] for i, s in enumerate(plan.rem))
    out = {"period": per, "rem": rem}
    if "pos" in tree:
        out["pos"] = tree["pos"]
    return out


def alloc_arena_kv(cfg, mesh, plan, n_arena_blocks, block_size, dtype=None,
                   quant: bool = False):
    """Allocate only the shared full-attention arenas:
    {"period": (entry|None, ...), "rem": (...)} with entry
    {"k","v": [n_rep?, n_arena_blocks, K, bs, h],
     "kmin","kmax","kmean": [n_rep?, n_arena_blocks, K, h] float32}
    (`n_arena_blocks` includes the reserved null block 0). The summary
    leaves are the per-block key metadata plane for online top-k block
    selection, maintained by the same donated jits that write KV — every
    arena K write recomputes the touched blocks' summaries, so a quiescent
    arena never holds a stale summary (KVArena.check_summaries asserts
    exactly that). kmin/kmax feed the Quest-style upper-bound score
    (kernels/block_topk.py); kmean is the block-center estimate (the
    mean-score ablation in bench_accuracy and diagnostics — not on the
    decode scoring path).

    With `quant` (QuantPlane, serving/quant.py) the k/v payloads are int8
    and each entry carries the scale plane: per-block PER-CHANNEL seal
    scales {"kscale","vscale": [n_rep?, N, K, h] f32} (nonzero row ⟺ block
    sealed) plus per-token scalar scales {"ktok","vtok": [n_rep?, N, K, bs]
    f32} for unsealed tail content — maintained by the same donated jits
    that write KV, so zero-stale-scale rides the summary invariant."""
    dtype = dtype or jnp.dtype(cfg.compute_dtype)
    if quant:
        dtype = jnp.int8
    K, h = cfg.n_kv_heads, cfg.head_dim
    kv_part = attn_mod.arena_kv_part(K, mesh.tp)

    def one(spec, stacked):
        if not full_attn_layer(cfg, spec):
            return None, None
        shp = (n_arena_blocks, K, block_size, h)
        sshp = (n_arena_blocks, K, h)
        tshp = (n_arena_blocks, K, block_size)
        lead = ()
        if stacked:
            shp = (plan.n_rep,) + shp
            sshp = (plan.n_rep,) + sshp
            tshp = (plan.n_rep,) + tshp
            lead = (None,)
        kv_sp = P(*lead, None, kv_part, None, None)
        sm_sp = P(*lead, None, kv_part, None)
        f32 = jnp.float32
        entry = {"k": jax.ShapeDtypeStruct(shp, dtype),
                 "v": jax.ShapeDtypeStruct(shp, dtype),
                 "kmin": jax.ShapeDtypeStruct(sshp, f32),
                 "kmax": jax.ShapeDtypeStruct(sshp, f32),
                 "kmean": jax.ShapeDtypeStruct(sshp, f32)}
        sps = {"k": kv_sp, "v": kv_sp,
               "kmin": sm_sp, "kmax": sm_sp, "kmean": sm_sp}
        if quant:
            entry.update(kscale=jax.ShapeDtypeStruct(sshp, f32),
                         vscale=jax.ShapeDtypeStruct(sshp, f32),
                         ktok=jax.ShapeDtypeStruct(tshp, f32),
                         vtok=jax.ShapeDtypeStruct(tshp, f32))
            sps.update(kscale=sm_sp, vscale=sm_sp,
                       ktok=sm_sp, vtok=sm_sp)
        return entry, sps

    period = [one(s, True) for s in plan.period]
    rem = [one(s, False) for s in plan.rem]
    sds = {"period": tuple(p[0] for p in period),
           "rem": tuple(r[0] for r in rem)}
    sps = {"period": tuple(p[1] for p in period),
           "rem": tuple(r[1] for r in rem)}
    return _alloc_placed(mesh, sds, sps)


def topk_block_budget(oa, nb: int) -> Optional[int]:
    """Static (per-trace) top-k block budget against a width-`nb` block
    table, or None when online sparsity is off (both budget knobs 0).
    Absolute `topk_blocks` wins over `topk_frac` (which resolves per slot
    in-trace against the RESIDENT block count — this static figure is its
    ceiling, ceil(frac·nb)). Floored at the forced keeps and capped at nb;
    a budget == nb means the (bucketed) table already fits the budget and
    the caller skips selection entirely — exact attention."""
    if oa.topk_blocks <= 0 and oa.topk_frac <= 0:
        return None
    k = oa.topk_blocks if oa.topk_blocks > 0 else \
        int(math.ceil(oa.topk_frac * nb))
    k = max(k, max(oa.topk_sink_blocks, 0) + max(oa.topk_recent_blocks, 1), 1)
    return min(k, nb)


def alloc_prefill_private_cache(cfg, mesh, plan, max_len, dtype=None):
    """B=1 dense task cache WITHOUT full-attention layers (their KV lives
    in the shared arena): ring KV (bounded by sink+recent), mamba state,
    and the position scalar. This is what a paged prefill task pins per
    layer instead of a [1, max_len, K, h] dense cache."""
    return _drop_entries(cfg, plan,
                         alloc_cache(cfg, mesh, plan, 1, max_len, dtype),
                         drop_full=True)


def alloc_paged_private_cache(cfg, mesh, plan, n_slots, max_len, block_size,
                              dtype=None):
    """Decode-engine private side of the paged cache: per-slot ring arenas
    and non-attention state; full-attention entries are None (shared
    arena). n_arena_blocks=1 below is a placeholder — those entries are
    dropped."""
    return _drop_entries(cfg, plan,
                         alloc_paged_cache(cfg, mesh, plan, n_slots, max_len,
                                           1, block_size, dtype),
                         drop_full=True)


def merge_arena_cache(cfg, plan, private, arena_kv):
    """(private ∪ arena) → the full cache pytree a jit body expects."""
    per = tuple(arena_kv["period"][i] if full_attn_layer(cfg, s)
                else private["period"][i] for i, s in enumerate(plan.period))
    rem = tuple(arena_kv["rem"][i] if full_attn_layer(cfg, s)
                else private["rem"][i] for i, s in enumerate(plan.rem))
    return {"period": per, "rem": rem, "pos": private["pos"]}


def split_arena_cache(cfg, plan, cache):
    """Inverse of merge_arena_cache → (private, arena_kv)."""
    return (_drop_entries(cfg, plan, cache, drop_full=True),
            _drop_entries(cfg, plan, {k: cache[k] for k in ("period", "rem")},
                          drop_full=False))


# ======================================================================
def unstack_params(plan: StackPlan, params: dict) -> list[dict]:
    """Stack params → flat per-layer list (layer order)."""
    layers = []
    for r in range(plan.n_rep):
        for i in range(len(plan.period)):
            layers.append(jax.tree.map(lambda x: x[r], params["period"][i]))
    layers.extend(params["rem"])
    return layers


def restack_params(plan: StackPlan, layers: list[dict]) -> dict:
    """Flat per-layer list → stack params for `plan`."""
    p = len(plan.period)
    period = []
    for i in range(p):
        entries = [layers[r * p + i] for r in range(plan.n_rep)]
        period.append(jax.tree.map(lambda *xs: jnp.stack(xs), *entries))
    rem = tuple(layers[plan.n_rep * p:])
    return {"period": tuple(period), "rem": rem}


def regroup_params(params: dict, plan_from: StackPlan, plan_to: StackPlan) -> dict:
    """Convert stack params between periodizations (e.g. to serve a model
    under a different OmniAttn pattern than it was built with). Weights are
    pattern-independent; only the scan grouping changes."""
    if plan_from == plan_to:
        return params
    if plan_from.n_layers != plan_to.n_layers:
        raise ValueError("layer count mismatch")
    return restack_params(plan_to, unstack_params(plan_from, params))


# ======================================================================
# Layer application
def _layer_leaves(leaves: dict, layer) -> dict:
    """One layer's slice of each leaf of a flat cache dict (the leaves
    themselves when unstacked) — for the Pallas kernels, which take
    unstacked arenas, and for dense reads of a whole layer."""
    return leaves if layer is None else {n: a[layer] for n, a in leaves.items()}


def attn_sublayer(cfg: ModelConfig, mesh: MeshCtx, p: dict, x, *, spec: LayerSpec,
                  mode: str, positions, cache, max_len: int, batch_part,
                  true_len=None, attend_limit: int = 0, block_tables=None,
                  token_mask=None, layer=None):
    """→ (x, new_cache | None, sparsity_aux | None). sparsity_aux is a [4]
    f32 vector [blocks_scored, blocks_attended, mass_sum, mass_n] emitted
    by paged-decode full-attention layers when online top-k selection is
    configured (cfg.omniattn.topk_*), weighted by `token_mask` (live
    slots). With `layer=i` the cache leaves are scan-stacked [n_rep, ...]
    and this is layer i of them: reads gather and writes scatter that
    layer's rows in place, and new_cache is the whole stacked entry."""
    B = x.shape[0]
    H, K, h = cfg.n_heads, cfg.n_kv_heads, cfg.head_dim
    cd = jnp.dtype(cfg.compute_dtype)
    hid = rms_norm(x, p["ln_attn"], cfg.rms_eps).astype(cd)
    q = hid @ p["wq"]
    k = hid @ p["wk"]
    v = hid @ p["wv"]
    if cfg.qkv_bias:
        q, k, v = q + p["bq"], k + p["bk"], v + p["bv"]
    S = x.shape[1]
    q = q.reshape(B, S, H, h)
    k = k.reshape(B, S, K, h)
    v = v.reshape(B, S, K, h)
    if cfg.qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.rms_eps)
        k = rms_norm(k, p["k_norm"], cfg.rms_eps)
    q = attn_mod.apply_rope(q, positions, cfg.rope_theta)
    k = attn_mod.apply_rope(k, positions, cfg.rope_theta)
    sink, recent = cache_window(cfg, spec)

    use_pallas = cfg.use_pallas and mesh.tp == 1
    new_cache = None
    sp_aux = None
    if (mode == "prefill" and cache is not None and block_tables is not None
            and not (sink or recent)):
        # paged chunked prefill (B=1 task): the prompt's resident history
        # lives in pool-allocated arena blocks reached through the task's
        # block table (cache leaves ARE the arenas); the chunk's K/V is
        # scattered straight into its own blocks — no dense [1, max_len]
        # cache ever exists for this layer. Ring layers (sink or recent)
        # stay on the dense per-task path below: their capacity is bounded
        # by the window, not max_len.
        cl = S if true_len is None else true_len
        pos0 = jnp.asarray(positions, jnp.int32)[0]
        # QuantPlane: int8 arenas carry the scale plane — history reads
        # dequantize in-tile, writes quantize per-token + seal-on-full
        quant = "kscale" in cache
        qkw = dict(k_scale=cache["kscale"], k_tok=cache["ktok"],
                   v_scale=cache["vscale"], v_tok=cache["vtok"]) \
            if quant else {}
        if use_pallas:
            from repro.kernels import ops as kops
            lc = _layer_leaves({"k": cache["k"], "v": cache["v"]}, layer)
            out = kops.attention_paged_prefill_op(
                q, k, v, lc["k"], lc["v"], block_tables, pos0, cl,
                **_layer_leaves(qkw, layer))
        else:
            out = attn_mod.paged_prefill_attention(
                q, k, v, cache["k"], cache["v"], block_tables, pos0, cl,
                layer=layer, **qkw)
        # the chunk lands after the history reads above
        wtbl = attn_mod.order_after(block_tables, out)
        if quant:
            new_cache = attn_mod.quant_paged_prefill_write(
                cache, k, v, wtbl, pos0, cl, layer=layer)
        else:
            kc, vc = attn_mod.paged_prefill_write(cache["k"], cache["v"],
                                                  k, v, wtbl, pos0, cl,
                                                  layer=layer)
            new_cache = {"k": kc, "v": vc}
        y = out.reshape(B, S, H * h)
        if "kmin" in cache:
            # block-summary metadata plane: the chunk's writes touched the
            # blocks its token positions map to (padded tail rows alias the
            # null block, whose re-summary is harmless) — recompute those
            # blocks' summaries from the updated arena in the same jit
            bs_a = cache["k"].shape[-2]
            nb_t = block_tables.shape[1]
            ppos = pos0 + jnp.arange(S)
            wblk = jnp.where(jnp.arange(S) < jnp.asarray(cl, jnp.int32),
                             block_tables[0, jnp.clip(ppos // bs_a, 0,
                                                      nb_t - 1)], 0)
            kmn, kmx, kme = attn_mod.update_block_summaries(
                cache["kmin"], cache["kmax"], cache["kmean"],
                new_cache["k"], wblk,
                k_scale=new_cache.get("kscale"),
                k_tok=new_cache.get("ktok"), layer=layer)
            new_cache.update(kmin=kmn, kmax=kmx, kmean=kme)
    elif mode == "prefill" and cache is not None:
        # continuation chunk (chunked prefill / radix prefix-KV resume):
        # attend resident cache tokens + causal in-chunk keys, then scatter
        # the chunk into the cache. true_len here is chunk-local.
        mask_window = mask_sink = 0
        if spec.window > 0:
            mask_window = spec.window
        elif spec.compressed and cfg.prefill_sparse:
            mask_window, mask_sink = recent, sink
        out, kc, vc = attn_mod.prefill_resume_attention(
            q, k, v, cache["k"], cache["v"], positions,
            chunk_len=(S if true_len is None else true_len),
            sink=sink, recent=recent,
            mask_window=mask_window, mask_sink=mask_sink,
            attend_limit=attend_limit, layer=layer)
        y = out.reshape(B, S, H * h)
        new_cache = {"k": kc, "v": vc}
    elif mode == "decode" and block_tables is not None:
        # physically paged decode: the cache leaves are block arenas
        # [N, K, bs, h]; full layers map logical position blocks through the
        # per-slot table, ring layers statically own a contiguous block run.
        pos = jnp.asarray(positions)
        t = pos[:, 0] if pos.ndim == 2 else (pos[0] if pos.ndim == 1 else pos)
        t = jnp.broadcast_to(jnp.asarray(t, jnp.int32), (B,))
        bs = cache["k"].shape[-2]
        bidx = jnp.arange(B, dtype=jnp.int32)
        if sink or recent:
            W = sink + recent
            bpw = ring_block_count(sink, recent, bs)
            slot = attn_mod.ring_slot(t, sink, recent)
            blk = bidx * bpw + slot // bs
            off = slot % bs
            tbl = bidx[:, None] * bpw + jnp.arange(bpw, dtype=jnp.int32)[None, :]
            lens = jnp.minimum(t + 1, W)
        else:
            # past the table's logical capacity the write is redirected to
            # the null block (the dense per-request path drops OOB writes)
            nb = block_tables.shape[1]
            blk = jnp.where(t < nb * bs,
                            block_tables[bidx, jnp.minimum(t // bs, nb - 1)],
                            0)
            off = t % bs
            tbl = block_tables
            lens = jnp.minimum(t + 1, nb * bs)
        quant = "kscale" in cache
        if quant and not (sink or recent):
            # QuantPlane append: per-token int8 quantize + scale-plane
            # maintenance (unseal-on-open / seal-on-full) in one helper
            new_cache = attn_mod.quant_paged_cache_write(
                cache, k[:, 0], v[:, 0], blk, off, layer=layer)
            kc, vc = new_cache["k"], new_cache["v"]
        else:
            kc, vc = attn_mod.paged_cache_write(cache["k"], cache["v"],
                                                k[:, 0], v[:, 0], blk, off,
                                                layer=layer)
            new_cache = {"k": kc, "v": vc}
        qkw = dict(k_scale=new_cache["kscale"], k_tok=new_cache["ktok"],
                   v_scale=new_cache["vscale"], v_tok=new_cache["vtok"]) \
            if quant and not (sink or recent) else {}
        if not (sink or recent) and "kmin" in cache:
            # summaries ride the same write: the appended token lands in
            # `blk` (freed slots alias the null block) — recompute those
            # blocks BEFORE scoring so the tail bound covers the new key
            kmn, kmx, kme = attn_mod.update_block_summaries(
                cache["kmin"], cache["kmax"], cache["kmean"], kc, blk,
                k_scale=new_cache.get("kscale"),
                k_tok=new_cache.get("ktok"), layer=layer)
            new_cache.update(kmin=kmn, kmax=kmx, kmean=kme)
            oa = cfg.omniattn
            k_static = topk_block_budget(oa, tbl.shape[1])
            if k_static is not None:
                act = (token_mask.astype(jnp.float32) if token_mask
                       is not None else jnp.ones((B,), jnp.float32))
                n_res = (lens + bs - 1) // bs
                scored = (act * n_res).sum()
                if k_static < tbl.shape[1]:
                    # online top-k: score resident blocks with the Quest
                    # upper bound and attend a compacted table — blocks
                    # outside the budget are never gathered downstream
                    if use_pallas:
                        from repro.kernels import ops as kops
                        sm = _layer_leaves({"lo": kmn, "hi": kmx}, layer)
                        scores = kops.block_topk_scores_op(
                            q[:, 0], sm["lo"], sm["hi"], tbl, lens,
                            block_size=bs)
                    else:
                        scores = attn_mod.block_topk_scores(
                            q[:, 0], kmn, kmx, tbl, lens, block_size=bs,
                            layer=layer)
                    tbl_s, lens_s, m, selected = attn_mod.select_kv_blocks(
                        scores, tbl, lens, block_size=bs, k_static=k_static,
                        frac=0.0 if oa.topk_blocks > 0 else oa.topk_frac,
                        sink_blocks=max(oa.topk_sink_blocks, 0),
                        recent_blocks=max(oa.topk_recent_blocks, 1))
                    if oa.topk_measure_mass:
                        mass = attn_mod.selected_attention_mass(
                            q[:, 0], kc, tbl, lens, selected,
                            k_scale=new_cache.get("kscale"),
                            k_tok=new_cache.get("ktok"), layer=layer)
                        mass_sum, mass_n = (act * mass).sum(), act.sum()
                    else:
                        mass_sum = mass_n = jnp.float32(0)
                    sp_aux = jnp.stack([scored, (act * m).sum(),
                                        mass_sum, mass_n])
                    tbl, lens = tbl_s, lens_s
                else:
                    # budget covers the whole (bucketed) table: exact
                    # attention; still report so the stats stay comparable
                    mn = act.sum() if oa.topk_measure_mass else jnp.float32(0)
                    sp_aux = jnp.stack([scored, scored, mn, mn])
        if use_pallas:
            from repro.kernels import ops as kops
            lc = _layer_leaves({"k": kc, "v": vc}, layer)
            out = kops.attention_paged_decode_op(
                q[:, 0], lc["k"], lc["v"], tbl, lens,
                **_layer_leaves(qkw, layer))
        else:
            out = attn_mod.paged_decode_attention(q[:, 0], kc, vc, tbl, lens,
                                                  layer=layer, **qkw)
        y = out.reshape(B, 1, H * h)
    elif mode == "verify":
        # speculative verify: READ-ONLY attention of each slot's draft
        # window [B, S] (S = k+1) against its paged history. No K/V write
        # happens here — the window's rope'd keys are STAGED as this
        # layer's "new cache" and committed post-acceptance by
        # stack_verify_commit, so a rejected draft row leaves blocks and
        # block summaries untouched (rollback = the write never landing).
        pos2 = jnp.asarray(positions, jnp.int32)        # [B, S]
        if sink or recent:
            # gather the slot's frozen ring blocks into a dense [B, W]
            # view (slot b statically owns blocks [b·bpw, (b+1)·bpw))
            lc = _layer_leaves({"k": cache["k"], "v": cache["v"]}, layer)
            bs_a = lc["k"].shape[2]
            bpw = ring_block_count(sink, recent, bs_a)
            W = sink + recent
            kr = jnp.moveaxis(lc["k"].reshape(B, bpw, K, bs_a, h), 2, 3) \
                .reshape(B, bpw * bs_a, K, h)[:, :W]
            vr = jnp.moveaxis(lc["v"].reshape(B, bpw, K, bs_a, h), 2, 3) \
                .reshape(B, bpw * bs_a, K, h)[:, :W]
            out = attn_mod.spec_verify_ring_attention(
                q, k, v, kr, vr, pos2, sink=sink, recent=recent)
        else:
            t = pos2[:, 0]
            qkw = dict(k_scale=cache["kscale"], k_tok=cache["ktok"],
                       v_scale=cache["vscale"], v_tok=cache["vtok"]) \
                if "kscale" in cache else {}
            if use_pallas:
                from repro.kernels import ops as kops
                lc = _layer_leaves({"k": cache["k"], "v": cache["v"]}, layer)
                out = kops.spec_verify_op(q, k, v, lc["k"], lc["v"],
                                          block_tables, t,
                                          jnp.full_like(t, S),
                                          **_layer_leaves(qkw, layer))
            else:
                out = attn_mod.paged_prefill_attention(
                    q, k, v, cache["k"], cache["v"], block_tables, t, S,
                    layer=layer, **qkw)
        y = out.reshape(B, S, H * h)
        new_cache = {"k": k, "v": v}
    elif mode == "decode":
        pos = jnp.asarray(positions)
        t = pos[:, 0] if pos.ndim == 2 else (pos[0] if pos.ndim == 1 else pos)
        kc, vc = attn_mod.cache_write(cache["k"], cache["v"], k[:, 0], v[:, 0], t,
                                      sink=sink, recent=recent, layer=layer)
        # dense attention reads the whole per-slot window of this layer
        lc = _layer_leaves({"k": kc, "v": vc}, layer)
        if use_pallas:
            from repro.kernels import ops as kops
            out = kops.attention_decode_op(q[:, 0], lc["k"], lc["v"], t + 1)
        else:
            strat = attn_mod.decode_strategy(K, mesh.tp)
            out = attn_mod.decode_attention(q[:, 0], lc["k"], lc["v"], t + 1,
                                            mesh=mesh, strategy=strat,
                                            batch_part=batch_part)
        y = out.reshape(B, 1, H * h)
        new_cache = {"k": kc, "v": vc}
    elif use_pallas:
        from repro.kernels import ops as kops
        window = spec.window
        use_sink = 0
        if spec.compressed and cfg.prefill_sparse:
            window, use_sink = recent, sink
        out = kops.attention_prefill_op(q, k, v, causal=cfg.causal,
                                        window=window, sink=use_sink)
        y = out.reshape(B, S, H * h)
        if mode == "prefill":
            if sink or recent:
                kc, vc = attn_mod.compress_prefill_kv(k, v, sink=sink,
                                                      recent=recent,
                                                      true_len=true_len)
            else:
                pad = max_len - S
                kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            new_cache = {"k": kc, "v": vc}
    else:
        window = spec.window
        use_sink = 0
        if spec.compressed and cfg.prefill_sparse:
            window, use_sink = recent, sink
        out = attn_mod.chunked_attention(
            q, k, v, causal=cfg.causal, window=window, sink=use_sink,
            q_chunk=cfg.attn_q_chunk, kv_chunk=cfg.attn_kv_chunk, mesh=mesh,
            strategy=attn_mod.prefill_strategy(H, K, mesh.tp),
            batch_part=batch_part,
            skip_masked_chunks=cfg.attn_skip_masked_chunks,
            fp32_scores=cfg.attn_fp32_scores,
            qseq_out_constraint=cfg.attn_qseq_out_constraint)
        y = out.reshape(B, S, H * h)
        if mode == "prefill":
            if sink or recent:
                kc, vc = attn_mod.compress_prefill_kv(k, v, sink=sink,
                                                      recent=recent,
                                                      true_len=true_len)
            else:
                pad = max_len - S
                kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
            new_cache = {"k": kc, "v": vc}
    y = (y @ p["wo"]).astype(x.dtype)
    return x + y, new_cache, sp_aux


def mamba_sublayer(cfg: ModelConfig, mesh: MeshCtx, p: dict, x, *, mode: str,
                   cache, batch_part, true_len=None):
    if mode == "verify":
        # backstop: SpecController refuses hybrid/SSM stacks upfront — a
        # rejected draft would need the pre-window recurrent state back,
        # and SSM state has no block/summary plane to roll back through
        raise NotImplementedError(
            "speculative verify has no multi-token SSM rollback path")
    B, S, D = x.shape
    ssm = cfg.ssm
    d_in = ssm.expand * D
    nh = d_in // ssm.head_dim
    N = ssm.d_state
    cd = jnp.dtype(cfg.compute_dtype)
    hid = rms_norm(x, p["ln_attn"], cfg.rms_eps).astype(cd)
    z = hid @ p["w_z"]
    xin = hid @ p["w_x"]
    bc = hid @ p["w_bc"]
    dt_raw = hid @ p["w_dt"]
    dt = jax.nn.softplus(dt_raw.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))
    A = -jnp.exp(p["A_log"].astype(jnp.float32))

    cx_cache = cache["conv_x"] if cache is not None else None
    cbc_cache = cache["conv_bc"] if cache is not None else None
    xin_pre, bc_pre = xin, bc               # pre-conv (cache rows live here)
    xin, new_cx = ssd_mod.causal_conv(xin, p["conv_x"], cx_cache)
    bc, new_cbc = ssd_mod.causal_conv(bc, p["conv_bc"], cbc_cache)
    xin = jax.nn.silu(xin)
    bc = jax.nn.silu(bc)
    if true_len is not None and mode != "decode":
        # right-padded prefill: dt=0 beyond true_len freezes the SSD state
        # (decay exp(0)=1, update 0); x masked for the D_skip term.
        live = (jnp.arange(S) < true_len)
        dt = dt * live[None, :, None]
        xin = xin * live[None, :, None].astype(xin.dtype)
        # conv caches hold the last conv_width-1 REAL pre-conv inputs; for a
        # continuation chunk they may straddle the chunk boundary, so slice
        # from (old cache ‖ chunk) instead of a zero-padded chunk.
        cw = ssm.conv_width
        if cx_cache is not None:
            pad_x = jnp.concatenate([cx_cache.astype(xin_pre.dtype), xin_pre],
                                    axis=1)
            pad_bc = jnp.concatenate([cbc_cache.astype(bc_pre.dtype), bc_pre],
                                     axis=1)
        else:
            pad_x = jnp.pad(xin_pre, ((0, 0), (cw - 1, 0), (0, 0)))
            pad_bc = jnp.pad(bc_pre, ((0, 0), (cw - 1, 0), (0, 0)))
        new_cx = jax.lax.dynamic_slice_in_dim(pad_x, true_len, cw - 1, axis=1)
        new_cbc = jax.lax.dynamic_slice_in_dim(pad_bc, true_len, cw - 1, axis=1)
    Bm, Cm = bc[..., :N], bc[..., N:]

    xh = xin.reshape(B, S, nh, ssm.head_dim)
    xh = mesh.constrain(xh, P(batch_part, None, "model", None))
    if mode == "decode":
        y1, new_state = ssd_mod.ssd_decode_step(cache["state"], xh[:, 0], dt[:, 0],
                                                A, Bm[:, 0], Cm[:, 0])
        y = y1[:, None]
    else:
        init = cache["state"] if cache is not None else None
        y, new_state = ssd_mod.ssd_chunked(xh, dt, A, Bm, Cm, ssm.chunk, init)
    y = y + xh.astype(y.dtype) * p["D_skip"].astype(y.dtype)[None, None, :, None]
    y = y.reshape(B, S, d_in)
    y = rms_norm(y * jax.nn.silu(z.astype(y.dtype)), p["ssm_norm"], cfg.rms_eps)
    out = (y.astype(cd) @ p["out_proj"]).astype(x.dtype)
    if mesh.tp > 1:
        out = mesh.constrain(out, P(batch_part, None, None))
    new_cache = None
    if mode in ("decode", "prefill"):
        new_cache = {"state": new_state.astype(jnp.float32), "conv_x": new_cx,
                     "conv_bc": new_cbc}
    return x + out, new_cache


def ffn_sublayer(cfg: ModelConfig, mesh: MeshCtx, p: dict, x, *, spec: LayerSpec,
                 batch_part, token_mask=None):
    """Returns (x, moe_counts or None)."""
    B, S, D = x.shape
    cd = jnp.dtype(cfg.compute_dtype)
    if not spec.use_moe and cfg.d_ff == 0:
        return x, None
    hid = rms_norm(x, p["ln_mlp"], cfg.rms_eps).astype(cd)
    if spec.use_moe:
        flat = hid.reshape(B * S, D)
        shared = None
        if cfg.moe.n_shared_experts:
            shared = (p["shared_w1"], p["shared_w3"], p["shared_w2"])
        tables = p["_tables"]
        y, counts = moe_mod.moe_ffn(mesh, cfg, flat, p["router"], p["moe_w1"],
                                    p["moe_w3"], p["moe_w2"], tables, shared,
                                    batch_part=batch_part,
                                    token_mask=token_mask)
        y = y.reshape(B, S, D)
        return x + y.astype(x.dtype), counts
    h1 = jax.nn.silu(hid @ p["w1"]) * (hid @ p["w3"])
    y = (h1 @ p["w2"]).astype(x.dtype)
    y = mesh.constrain(y, P(batch_part, None, None))
    return x + y, None


def apply_layer(cfg, mesh, spec: LayerSpec, p: dict, x, *, mode, positions,
                cache, max_len, batch_part, true_len=None, attend_limit=0,
                token_mask=None, block_tables=None, layer=None):
    """One layer. `layer=i`: the cache leaves are scan-stacked and this is
    layer i of them; the returned cache is then the updated stacked entry."""
    sp = None
    if spec.kind == "attn":
        x, nc, sp = attn_sublayer(cfg, mesh, p, x, spec=spec, mode=mode,
                                  positions=positions, cache=cache,
                                  max_len=max_len, batch_part=batch_part,
                                  true_len=true_len,
                                  attend_limit=attend_limit,
                                  block_tables=block_tables,
                                  token_mask=token_mask, layer=layer)
    else:
        # the recurrent state is rewritten whole every call
        x, nc = mamba_sublayer(cfg, mesh, p, x, mode=mode,
                               cache=(None if cache is None else
                                      _layer_leaves(cache, layer)),
                               batch_part=batch_part, true_len=true_len)
        if layer is not None and nc is not None:
            nc = {n: cache[n].at[layer].set(a) for n, a in nc.items()}
    x, counts = ffn_sublayer(cfg, mesh, p, x, spec=spec, batch_part=batch_part,
                             token_mask=token_mask)
    x = mesh.constrain(x, P(batch_part, None, None))
    return x, nc, counts, sp


# ======================================================================
def stack_apply(cfg: ModelConfig, mesh: MeshCtx, plan: StackPlan, params: dict,
                x, *, mode: str, positions, caches=None, max_len: int = 0,
                batch_part=None, tables=None, true_len=None,
                attend_limit: int = 0, token_mask=None, block_tables=None):
    """Run the full layer stack.

    tables: MoE placement tables dict (injected into layer params as '_tables').
    block_tables: [B, nb] physical KV block ids (decode over paged caches —
    every attention layer's cache leaves must then be block arenas).
    Returns (x, new_caches | None, aux dict with per-layer MoE counts).
    """
    def with_tables(p):
        if tables is not None and any(k.startswith("moe_") for k in p):
            p = dict(p)
            p["_tables"] = tables
        return p

    # An input cache rides the scan's carry, whole and stacked, and each
    # layer reads and writes only its own rows of it by the scan counter:
    # as xs/ys every layer would slice its whole cache out and write it
    # back into a fresh stacked buffer. Caches built here (prefill with no
    # input cache) and verify's staged window K/V come out as ys.
    has_cache = caches is not None
    writes = has_cache and mode != "verify"
    period_caches = caches["period"] if has_cache \
        else tuple(None for _ in plan.period)

    def body(carry, xs):
        h, cs = carry
        p_slices, li = xs
        cs, new_cs, counts, sps = list(cs), [], [], []
        for i, spec in enumerate(plan.period):
            h, nc, cnt, sp = apply_layer(cfg, mesh, spec,
                                         with_tables(p_slices[i]), h,
                                         mode=mode, positions=positions,
                                         cache=cs[i], max_len=max_len,
                                         batch_part=batch_part,
                                         true_len=true_len,
                                         attend_limit=attend_limit,
                                         token_mask=token_mask,
                                         block_tables=block_tables,
                                         layer=li if has_cache else None)
            if writes:
                cs[i] = nc
            elif nc is not None:
                new_cs.append(nc)
            if cnt is not None:
                counts.append(cnt)
            if sp is not None:
                sps.append(sp)
        return (h, tuple(cs)), (tuple(new_cs), tuple(counts), tuple(sps))

    if cfg.remat and mode == "train":
        policy = (jax.checkpoint_policies.dots_saveable
                  if cfg.remat_policy == "dots"
                  else jax.checkpoint_policies.nothing_saveable)
        body = jax.checkpoint(body, policy=policy)

    if plan.n_rep > 0 and plan.period:
        (x, carried), (new_period_caches, period_counts, period_sparsity) = \
            jax.lax.scan(body, (x, period_caches),
                         (params["period"], jnp.arange(plan.n_rep)))
        if writes:
            new_period_caches = carried
    else:
        carried = period_caches
        new_period_caches, period_counts, period_sparsity = (), (), ()

    new_rem_caches, rem_counts, rem_sparsity = [], [], []
    rem_caches = caches["rem"] if has_cache else tuple(None for _ in plan.rem)
    for i, spec in enumerate(plan.rem):
        x, nc, cnt, sp = apply_layer(cfg, mesh, spec,
                                     with_tables(params["rem"][i]), x,
                                     mode=mode, positions=positions,
                                     cache=rem_caches[i], max_len=max_len,
                                     batch_part=batch_part, true_len=true_len,
                                     attend_limit=attend_limit,
                                     token_mask=token_mask,
                                     block_tables=block_tables)
        if nc is not None:
            new_rem_caches.append(nc)
        if cnt is not None:
            rem_counts.append(cnt)
        if sp is not None:
            rem_sparsity.append(sp)

    new_caches = None
    if mode in ("prefill", "decode"):
        new_pos = jnp.max(jnp.asarray(positions)) + 1
        new_caches = {"period": new_period_caches, "rem": tuple(new_rem_caches),
                      "pos": jnp.asarray(new_pos, jnp.int32)}
    elif mode == "verify":
        # STAGED (not yet written) rope'd window K/V per attention layer —
        # period entries arrive scan-stacked [n_rep, B, S, K, h]. The caller
        # decides acceptance, then lands only the accepted prefix via
        # stack_verify_commit; until then the real caches are untouched.
        # "cache" is the input cache as the scan carried it, unchanged: the
        # commit writes into those buffers, so the input needs no copy.
        new_caches = {"period": new_period_caches,
                      "rem": tuple(new_rem_caches),
                      "cache": dict(caches, period=carried)}
    aux = {"period_counts": period_counts, "rem_counts": tuple(rem_counts),
           # per-layer online-sparsity vectors [blocks_scored,
           # blocks_attended, mass_sum, mass_n] — period entries arrive
           # scan-stacked [n_rep, 4]; empty tuples when sparsity is off
           "period_sparsity": period_sparsity,
           "rem_sparsity": tuple(rem_sparsity)}
    return x, new_caches, aux


# ======================================================================
def stack_verify_commit(cfg: ModelConfig, plan: StackPlan, staged,
                        positions, n_write, block_tables):
    """Land a speculative verify window's ACCEPTED prefix in the paged caches.

    staged: stack_apply(mode="verify")'s second return — each attention
    layer's rope'd window K/V, and under "cache" the paged cache pytree
    the verify forward read (untouched by it); positions [B] window start (the
    pre-verify slot cursor); n_write [B] rows to land per slot — the
    CONSUMED input tokens (current token + accepted drafts; 0 for idle
    slots); block_tables [B, nb].

    Window row i of slot b lands at absolute position positions[b] + i iff
    i < n_write[b]. Full-attention layers redirect rejected/idle/overflow
    rows to the null block and recompute the touched blocks' summaries in
    the same jit (duplicate + null ids are harmless re-reductions), so the
    zero-stale-summary invariant holds at the jit boundary — a rollback is
    simply a write that never happened. Ring layers have no null block:
    rejected rows write back their target slot's current content
    (gather-then-where), bit-exact no-ops. Distinct window rows always map
    to distinct ring slots because S ≤ recent (`chunked_prefill_support`
    caps the draft window). Returns the updated cache pytree; "pos"
    advances to the furthest committed cursor like a decode step's cache.
    """
    caches = staged["cache"]
    positions = jnp.asarray(positions, jnp.int32)
    n_write = jnp.asarray(n_write, jnp.int32)
    B = positions.shape[0]
    entries = list(staged["period"]) + list(staged["rem"])
    S = entries[0]["k"].shape[-3]
    pos2 = positions[:, None] + jnp.arange(S, dtype=jnp.int32)[None]
    valid = jnp.arange(S, dtype=jnp.int32)[None] < n_write[:, None]
    bidx = jnp.arange(B, dtype=jnp.int32)

    def commit_full(entry, stg, layer):
        bs = entry["k"].shape[-2]
        nb = block_tables.shape[1]
        blk = jnp.where(valid & (pos2 < nb * bs),
                        block_tables[bidx[:, None],
                                     jnp.minimum(pos2 // bs, nb - 1)], 0)
        off = pos2 % bs
        if "kscale" in entry:
            # QuantPlane commit: the staged f32 window quantizes per-token
            # on landing; rejected rows arrive null-redirected, so rollback
            # stays "the write never happened" for payload AND scale plane
            out = dict(entry)
            out.update(attn_mod.quant_paged_cache_write_tokens(
                entry, stg["k"], stg["v"], blk, off, layer=layer))
        else:
            kc, vc = attn_mod.paged_cache_write_tokens(
                entry["k"], entry["v"], stg["k"], stg["v"], blk, off,
                layer=layer)
            out = dict(entry, k=kc, v=vc)
        if "kmin" in entry:
            kmn, kmx, kme = attn_mod.update_block_summaries(
                entry["kmin"], entry["kmax"], entry["kmean"], out["k"],
                blk.reshape(-1), k_scale=out.get("kscale"),
                k_tok=out.get("ktok"), layer=layer)
            out.update(kmin=kmn, kmax=kmx, kmean=kme)
        return out

    def commit_ring(entry, stg, layer, sink, recent):
        bs = entry["k"].shape[-2]
        bpw = ring_block_count(sink, recent, bs)
        slot = attn_mod.ring_slot(pos2, sink, recent)
        blk = bidx[:, None] * bpw + slot // bs
        off = slot % bs
        kc, vc = attn_mod.paged_cache_write_tokens_masked(
            entry["k"], entry["v"], stg["k"], stg["v"], blk, off, valid,
            layer=layer)
        return dict(entry, k=kc, v=vc)

    def commit(spec, entry, stg, stacked):
        # a stacked entry lands every layer's window in one scatter
        layer = jnp.arange(plan.n_rep) if stacked else None
        sink, recent = cache_window(cfg, spec)
        if sink or recent:
            return commit_ring(entry, stg, layer, sink, recent)
        return commit_full(entry, stg, layer)

    per = tuple(commit(s, caches["period"][i], staged["period"][i], True)
                for i, s in enumerate(plan.period))
    rem = tuple(commit(s, caches["rem"][i], staged["rem"][i], False)
                for i, s in enumerate(plan.rem))
    new_pos = jnp.max(positions + n_write).astype(jnp.int32)
    return {"period": per, "rem": rem, "pos": new_pos}
