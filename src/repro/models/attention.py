"""Attention: chunked (flash-style) prefill + cached decode.

Layouts:
  q        [B, S, H, h]       (H = n_heads)
  k, v     [B, S, K, h]       (K = n_kv_heads, G = H//K)
  cache    [B, W, K, h]       per layer; W = allocated window

Every cache helper below also takes a scan-stacked leaf [n_rep, ...] with
`layer=i` (the layer scan's counter): reads gather and writes scatter at
that layer's rows only, so the stack never slices a whole layer out or
writes one back (`_at`).

Sharding strategies (chosen per arch by the caller — see DESIGN.md):
  prefill: 'heads' → shard H over `model` (repeat-kv full-head layout)
           'qseq'  → shard q-chunk seq over `model` (few-head archs)
  decode:  'kv'    → shard K over `model` (K ≥ TP)
           'wseq'  → shard cache W over `model` (flash-decoding style; XLA
                     inserts the LSE-combining all-reduce in the softmax)
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.distributed.ctx import MeshCtx
from repro.models.common import apply_rope  # re-export for layer code

NEG_INF = -1e30


def prefill_strategy(n_heads: int, n_kv: int, tp: int) -> str:
    return "heads" if n_heads % tp == 0 else "qseq"


def decode_strategy(n_kv: int, tp: int) -> str:
    return "kv" if n_kv % tp == 0 else "wseq"


def arena_kv_part(n_kv: int, tp: int):
    """Mesh axis (or None) the KV-head dim of paged KV arenas and their
    block summaries shards over. Blocks stay replicated along the block
    dim — any rank can serve any block-table row — so TP only splits the
    head dim, and only under the 'kv' decode strategy."""
    return "model" if tp > 1 and decode_strategy(n_kv, tp) == "kv" else None


def order_after(idx, x):
    """`idx` (int32 indices below 2**31 - 2) itself, computed after `x`.

    A scatter indexed by it waits for every read `x` depends on, so XLA
    may update the buffer those reads came from in place. Without such a
    data dependence XLA copies the whole buffer before the scatter, and an
    optimization barrier does not order them."""
    top = jnp.int32(2**31 - 1) - jnp.isnan(x).any().astype(jnp.int32)
    return jnp.minimum(idx, top)


def _at(layer, *idx):
    """Index tuple into a cache leaf: `idx` into layer `layer` of a
    scan-stacked leaf [n_rep, ...], or into the unstacked leaf itself when
    `layer` is None. `layer` [n_rep] (`jnp.arange(n_rep)`) indexes every
    layer at once, as a leading index dim: a slice there would make XLA
    transpose the whole leaf around the scatter. Index arrays must stay
    adjacent (no slice between them) so the indexed dims keep their
    place."""
    if layer is None:
        return idx
    if jnp.ndim(layer) == 1:
        layer = jnp.reshape(layer, (-1,) + (1,) * max(map(jnp.ndim, idx)))
    return (layer,) + idx


# ----------------------------------------------------------------------
def chunked_attention(
    q, k, v, *,
    causal: bool,
    window: int = 0,
    sink: int = 0,
    q_offset: int = 0,
    q_chunk: int = 2048,
    kv_chunk: int = 1024,
    mesh: Optional[MeshCtx] = None,
    strategy: str = "heads",
    batch_part=None,
    skip_masked_chunks: bool = False,
    fp32_scores: bool = True,
    qseq_out_constraint: bool = False,
):
    """Blockwise attention with online softmax; O(q_chunk·kv_chunk) live scores.

    window > 0 → sliding-window (local) attention of that width.
    skip_masked_chunks → unroll q chunks in Python and statically slice the KV
    range each q chunk can see (halves causal FLOPs; §Perf lever).
    """
    B, S, H, h = q.shape
    Skv = k.shape[1]
    K = k.shape[2]
    G = H // K
    scale = h ** -0.5

    qc = min(q_chunk, S)
    while S % qc:
        qc //= 2
    kc = min(kv_chunk, Skv)
    while Skv % kc:
        kc //= 2
    n_q, n_kv = S // qc, Skv // kc

    if strategy == "heads" and mesh is not None and mesh.tp > 1:
        # full-head layout: repeat KV → shard H over model
        k = jnp.repeat(k, G, axis=2)
        v = jnp.repeat(v, G, axis=2)
        K_eff, G_eff = H, 1
        head_spec = "model"
    else:
        K_eff, G_eff = K, G
        head_spec = None

    kq = k.reshape(B, n_kv, kc, K_eff, h)
    vq = v.reshape(B, n_kv, kc, K_eff, h)
    qr = q.reshape(B, n_q, qc, K_eff, G_eff, h)

    def one_q_chunk(args):
        qi, q_blk = args                      # q_blk [B, qc, K_eff, G_eff, h]
        if mesh is not None:
            if strategy == "qseq":
                q_blk = mesh.constrain(q_blk, P(batch_part, "model", None, None, None))
            elif head_spec:
                q_blk = mesh.constrain(q_blk, P(batch_part, None, head_spec, None, None))
        q_pos = q_offset + qi * qc + jnp.arange(qc)

        sd = jnp.float32 if fp32_scores else q.dtype

        def kv_step(carry, inp):
            m, l, acc = carry
            kj, k_blk, v_blk = inp            # [B, kc, K_eff, h]
            k_pos = kj * kc + jnp.arange(kc)
            s = jnp.einsum("bqkgh,bckh->bkgqc", q_blk.astype(sd),
                           k_blk.astype(sd)) * scale
            mask = jnp.ones((qc, kc), dtype=bool)
            if causal:
                mask &= k_pos[None, :] <= q_pos[:, None]
            if window > 0:
                in_win = (q_pos[:, None] - k_pos[None, :]) < window
                if sink > 0:          # sink+window sparse prefill (OmniAttn)
                    in_win |= k_pos[None, :] < sink
                mask &= in_win
            s = jnp.where(mask[None, None, None], s,
                          jnp.asarray(NEG_INF, s.dtype))
            m_new = jnp.maximum(m, s.max(axis=-1).astype(jnp.float32))
            p = jnp.exp(s.astype(jnp.float32) - m_new[..., None]).astype(sd) \
                if fp32_scores else jnp.exp(s - m_new[..., None].astype(sd))
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(axis=-1).astype(jnp.float32)
            pv = jnp.einsum("bkgqc,bckh->bkgqh", p.astype(v_blk.dtype), v_blk)
            acc_new = acc * corr[..., None].astype(acc.dtype) + pv
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, K_eff, G_eff, qc), NEG_INF, dtype=jnp.float32)
        l0 = jnp.zeros((B, K_eff, G_eff, qc), dtype=jnp.float32)
        a0 = jnp.zeros((B, K_eff, G_eff, qc, h), dtype=v.dtype)

        if skip_masked_chunks and causal:
            # statically bound the visible kv blocks for this q chunk:
            # causal upper bound, sliding-window lower bound, sink blocks
            # (OmniAttn sparse prefill: compute ∝ window, not S)
            q_lo = q_offset + int(qi) * qc
            q_hi = q_offset + (int(qi) + 1) * qc - 1
            hi = min(n_kv, (q_hi + kc) // kc)
            if window > 0:
                lo = max(0, (q_lo - window + 1) // kc)
                vis = set(range(lo, hi))
                if sink > 0:
                    vis |= set(range(0, min((sink + kc - 1) // kc, n_kv)))
            else:
                vis = set(range(hi))
            carry = (m0, l0, a0)
            for j in sorted(vis):
                carry, _ = kv_step(carry, (jnp.asarray(j), kq[:, j], vq[:, j]))
            m, l, acc = carry
        else:
            (m, l, acc), _ = jax.lax.scan(
                kv_step, (m0, l0, a0),
                (jnp.arange(n_kv), jnp.moveaxis(kq, 1, 0), jnp.moveaxis(vq, 1, 0)))
        out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
        out = jnp.moveaxis(out, 3, 1)          # [B, qc, K_eff, G_eff, h]
        return out.reshape(B, qc, K_eff * G_eff, h)

    if skip_masked_chunks and causal:
        outs = [one_q_chunk((i, qr[:, i])) for i in range(n_q)]
        out = jnp.stack(outs, axis=1)
    else:
        out = jax.lax.map(one_q_chunk, (jnp.arange(n_q), jnp.moveaxis(qr, 1, 0)))
        out = jnp.moveaxis(out, 0, 1)         # [B, n_q, qc, H, h]
    out = out.reshape(B, S, H, h)
    if mesh is not None and strategy == "qseq" and qseq_out_constraint:
        # pin the q-sequence sharding on the merged output so SPMD reshards
        # once at the wo matmul instead of inventing 6-D transposes
        # (cuts collectives ~12% but costs compute — §Perf C1: net refuted,
        # kept as an opt-in knob)
        out = mesh.constrain(out, P(batch_part, "model", None, None))
    return out


# ----------------------------------------------------------------------
def decode_attention(
    q, k_cache, v_cache, t, *,
    mesh: Optional[MeshCtx] = None,
    strategy: str = "kv",
    batch_part=None,
):
    """Single-token attention over a cache. q [B, H, h]; caches [B, W, K, h];
    t = number of tokens written (all cache slots with idx < min(t, W) valid —
    ring layout guarantees slots [0, min(t,W)) are occupied)."""
    B, H, h = q.shape
    W, K = k_cache.shape[1], k_cache.shape[2]
    G = H // K
    scale = h ** -0.5

    if mesh is not None:
        w_part = "model" if strategy == "wseq" else None
        kv_part = "model" if strategy == "kv" else None
        cache_spec = P(batch_part, w_part, kv_part, None)
        k_cache = mesh.constrain(k_cache, cache_spec)
        v_cache = mesh.constrain(v_cache, cache_spec)

    qg = q.reshape(B, K, G, h).astype(jnp.float32)
    s = jnp.einsum("bkgh,bwkh->bkgw", qg, k_cache.astype(jnp.float32)) * scale
    t = jnp.asarray(t)
    lim = jnp.minimum(t, W)
    if lim.ndim:                      # per-request positions [B]
        lim = lim[:, None, None, None]
    valid = jnp.arange(W)[None, None, None, :] < lim
    s = jnp.where(valid, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bkgw,bwkh->bkgh", p.astype(v_cache.dtype), v_cache)
    return out.reshape(B, H, h)


# ----------------------------------------------------------------------
# QuantPlane: int8 arena payloads + the f32 scale plane.
#
# Sealed (full) blocks store K/V as int8 with per-block, PER-CHANNEL f32
# scales [N, K, h] (kscale/vscale); the partial tail block's tokens carry
# per-token, per-kv-head SCALAR scales [N, K, bs] (ktok/vtok), assigned
# once when the token is appended. The per-token scale is a pure function
# of the single token and the per-channel seal scale a pure function of
# the block's stored int payload, so the arena bytes are independent of
# how writes were grouped into chunks/windows — the bit-identity contracts
# (chunked prefill vs store-resume vs verify commits vs fault replay) ride
# on exactly this grouping independence. Convention: a nonzero kscale row
# marks the block sealed; dequantization is the single elementwise rule
# `q * where(scale != 0, scale, tok)` which is exact in every edge case
# (zero channels of sealed blocks have q == 0, scrubbed blocks dequantize
# to 0) and needs no residency context.


def quant_tokens(x):
    """Per-token provisional int8 quantization (the unsealed tail format).

    x [..., h] → (q int8 [..., h], ts f32 [...]): ts = absmax(token)/127
    per (token, kv head); q = round(x/ts) clipped to ±127. Zero tokens get
    ts = 0 with q = 0 (the dequant rule multiplies by the stored 0)."""
    x = jnp.asarray(x, jnp.float32)
    ts = jnp.abs(x).max(axis=-1) / 127.0
    safe = jnp.where(ts > 0, ts, 1.0)
    q = jnp.clip(jnp.round(x / safe[..., None]), -127, 127).astype(jnp.int8)
    return q, ts


def quant_effective_scale(scale, tok):
    """Elementwise dequant scale [..., bs, h] from the per-channel seal
    plane `scale` [..., h] and the per-token plane `tok` [..., bs]: sealed
    blocks (nonzero scale row) use the channel scale, unsealed content the
    token scalar."""
    return jnp.where(scale[..., None, :] != 0, scale[..., None, :],
                     tok[..., None])


def dequant_pages(pages, scale, tok):
    """int8 payload [..., bs, h] → f32 content, via the elementwise rule."""
    return pages.astype(jnp.float32) * quant_effective_scale(scale, tok)


def dequant_gather(pages, scale, tok, tables, *, layer=None):
    """Gather tabled blocks and dequantize → linear [B, nb·bs, K, h] f32
    (the quant twin of the `k_pages[tables]` gathers below)."""
    B, nb = tables.shape
    K, bs, h = pages.shape[-3:]
    ix = _at(layer, tables)
    g = dequant_pages(pages[ix], scale[ix], tok[ix])
    return g.transpose(0, 1, 3, 2, 4).reshape(B, nb * bs, K, h)


def seal_blocks(pages, scale, tok, blocks, do_seal, *, layer=None):
    """Seal freshly-filled arena blocks: re-quantize each block's stored
    per-token payload with per-block, per-channel scales and zero its
    per-token row. pages int8 [n_rep?, N, K, bs, h]; scale [n_rep?, N, K, h];
    tok [n_rep?, N, K, bs] (stacked leaves take `layer`, see `_at`);
    blocks [M] physical ids; do_seal [M] bool.

    Non-sealing rows are redirected to the null block 0 and write back its
    gathered content unchanged (whole-block scatters must keep real-block
    targets unique — the duplicate-scatter determinism rule); the null
    block itself is never sealed. Sealing is a pure function of the stored
    (int8, per-token scale) payload, so it lands the same bytes no matter
    which write grouping filled the block."""
    blocks = jnp.asarray(blocks, jnp.int32)
    do_seal = jnp.asarray(do_seal, bool) & (blocks != 0)
    tgt = jnp.where(do_seal, blocks, 0)
    ix = _at(layer, tgt)
    praw = pages[ix]                               # [R?, M, K, bs, h] int8
    ts = tok[ix]                                   # [R?, M, K, bs]
    deq = praw.astype(jnp.float32) * ts[..., None]
    sc = jnp.abs(deq).max(axis=-2) / 127.0         # [R?, M, K, h]
    safe = jnp.where(sc > 0, sc, 1.0)
    q2 = jnp.clip(jnp.round(deq / safe[..., None, :]), -127, 127) \
        .astype(jnp.int8)
    m5 = do_seal.reshape(-1, 1, 1, 1)
    m4 = do_seal.reshape(-1, 1, 1)
    return (pages.at[ix].set(jnp.where(m5, q2, praw)),
            scale.at[ix].set(jnp.where(m4, sc, scale[ix])),
            tok.at[ix].set(jnp.where(m4, 0.0, ts)))


def quant_paged_cache_write(entry, k_new, v_new, blk, off, *, layer=None):
    """Decode append into an int8 arena entry (the quant twin of
    `paged_cache_write` + the scale-plane maintenance it implies).

    entry holds {"k","v"} int8 arenas plus {"kscale","vscale","ktok",
    "vtok"}; k_new/v_new [B, K, h] f32; blk/off [B]. Three scatters in
    order: (1) UNSEAL any block receiving its in-block offset-0 token —
    clearing the per-channel scale a prior owner may have sealed in
    (reallocated blocks are not scrubbed; without this the dequant rule
    would read the stale seal scale over the new owner's per-token
    payload); (2) write the per-token quantized payload + its scale;
    (3) SEAL blocks whose last slot (off == bs-1) just landed. Returns the
    six updated quant leaves."""
    bs = entry["k"].shape[-2]
    K = entry["k"].shape[-3]
    kq, kts = quant_tokens(k_new)
    vq, vts = quant_tokens(v_new)
    ub = _at(layer, jnp.where(off == 0, blk, 0))
    ksc = entry["kscale"].at[ub].set(0.0)
    vsc = entry["vscale"].at[ub].set(0.0)
    kp, vp = paged_cache_write(entry["k"], entry["v"], kq, vq, blk, off,
                               layer=layer)
    ki = jnp.arange(K)[None, :]
    ix = _at(layer, blk[:, None], ki, off[:, None])
    ktk = entry["ktok"].at[ix].set(kts)
    vtk = entry["vtok"].at[ix].set(vts)
    do_seal = off == bs - 1
    kp, ksc, ktk = seal_blocks(kp, ksc, ktk, blk, do_seal, layer=layer)
    vp, vsc, vtk = seal_blocks(vp, vsc, vtk, blk, do_seal, layer=layer)
    return {"k": kp, "v": vp, "kscale": ksc, "vscale": vsc,
            "ktok": ktk, "vtok": vtk}


def quant_paged_prefill_write(entry, k_new, v_new, tables, off, chunk_len,
                              *, layer=None):
    """Chunk scatter into an int8 arena entry (quant twin of
    `paged_prefill_write`): per-token quantize the chunk [1, S, K, h],
    unseal blocks the chunk opens (first token at in-block offset 0), land
    payload + per-token scales, then seal every block whose last slot the
    chunk covered. Padded tail rows are redirected to the null block."""
    B, S, K, h = k_new.shape
    bs = entry["k"].shape[-2]
    nb = tables.shape[1]
    pos = jnp.asarray(off, jnp.int32) + jnp.arange(S)
    valid = jnp.arange(S) < jnp.asarray(chunk_len, jnp.int32)
    blk = jnp.where(valid, tables[0, jnp.clip(pos // bs, 0, nb - 1)], 0)
    offi = pos % bs
    kq, kts = quant_tokens(k_new[0])               # [S, K, h], [S, K]
    vq, vts = quant_tokens(v_new[0])
    ub = _at(layer, jnp.where(valid & (offi == 0), blk, 0))
    ksc = entry["kscale"].at[ub].set(0.0)
    vsc = entry["vscale"].at[ub].set(0.0)
    ix = _at(layer, blk[:, None], jnp.arange(K)[None, :], offi[:, None])
    kp = entry["k"].at[ix].set(kq)
    vp = entry["v"].at[ix].set(vq)
    ktk = entry["ktok"].at[ix].set(kts)
    vtk = entry["vtok"].at[ix].set(vts)
    do_seal = valid & (offi == bs - 1)
    kp, ksc, ktk = seal_blocks(kp, ksc, ktk, blk, do_seal, layer=layer)
    vp, vsc, vtk = seal_blocks(vp, vsc, vtk, blk, do_seal, layer=layer)
    return {"k": kp, "v": vp, "kscale": ksc, "vscale": vsc,
            "ktok": ktk, "vtok": vtk}


def quant_paged_cache_write_tokens(entry, k_new, v_new, blk, off, *,
                                   layer=None):
    """Per-sequence token-WINDOW scatter into an int8 arena entry (quant
    twin of `paged_cache_write_tokens` — the speculative-verify commit).
    blk/off [B, S]; rejected/idle rows arrive already redirected to the
    null block, so rollback stays the absence of a write; unseal/seal
    follow the same offset-0 / offset-(bs-1) rules as the append path."""
    K = k_new.shape[-2]
    bs = entry["k"].shape[-2]
    kq, kts = quant_tokens(k_new)                  # [.., B, S, K, h], [.., K]
    vq, vts = quant_tokens(v_new)
    ub = _at(layer, jnp.where(off == 0, blk, 0).reshape(-1))
    ksc = entry["kscale"].at[ub].set(0.0)
    vsc = entry["vscale"].at[ub].set(0.0)
    ix = _at(layer, blk[:, :, None], jnp.arange(K)[None, None, :],
             off[:, :, None])
    kp = entry["k"].at[ix].set(kq)
    vp = entry["v"].at[ix].set(vq)
    ktk = entry["ktok"].at[ix].set(kts)
    vtk = entry["vtok"].at[ix].set(vts)
    flat_b = blk.reshape(-1)
    do_seal = (off == bs - 1).reshape(-1)
    kp, ksc, ktk = seal_blocks(kp, ksc, ktk, flat_b, do_seal, layer=layer)
    vp, vsc, vtk = seal_blocks(vp, vsc, vtk, flat_b, do_seal, layer=layer)
    return {"k": kp, "v": vp, "kscale": ksc, "vscale": vsc,
            "ktok": ktk, "vtok": vtk}


# ----------------------------------------------------------------------
def paged_decode_attention(q, k_pages, v_pages, tables, lens, *,
                           k_scale=None, k_tok=None, v_scale=None,
                           v_tok=None, layer=None):
    """Single-token attention over physically paged KV (pure-jnp path).

    q [B, H, h]; arenas [N, K, bs, h] (kv-head-major blocks); tables [B, nb]
    physical block ids; lens [B] = resident logical slots (t+1 once the
    current token's K/V is written; min(t+1, W) for ring layers). Gathers
    the tabled blocks into a linear [B, nb·bs, K, h] view (non-resident
    entries alias the null block and are masked by `lens`) and reuses the
    dense masked-softmax decode math. With the scale-plane kwargs the
    arenas are int8 and each gathered tile is dequantized in-register
    (quant_effective_scale) — no dequantized arena copy exists outside the
    gathered view. The Pallas kernel additionally skips compute for blocks
    past `lens` — this fallback pays the full gather.
    """
    B = q.shape[0]
    nb = tables.shape[1]
    K, bs, h = k_pages.shape[-3:]
    if k_scale is not None:
        k_lin = dequant_gather(k_pages, k_scale, k_tok, tables, layer=layer)
        v_lin = dequant_gather(v_pages, v_scale, v_tok, tables, layer=layer)
    else:
        ix = _at(layer, tables)
        k_lin = k_pages[ix].transpose(0, 1, 3, 2, 4) \
            .reshape(B, nb * bs, K, h)
        v_lin = v_pages[ix].transpose(0, 1, 3, 2, 4) \
            .reshape(B, nb * bs, K, h)
    return decode_attention(q, k_lin, v_lin, lens)


def paged_prefill_attention(q, k_new, v_new, k_pages, v_pages, tables, off,
                            chunk_len, *, mask_window: int = 0,
                            mask_sink: int = 0, k_scale=None, k_tok=None,
                            v_scale=None, v_tok=None, layer=None):
    """Chunked-prefill attention over paged history (pure-jnp path).

    q [B,S,H,h] is one prompt chunk at absolute positions off + arange(S)
    (only the first chunk_len rows real); k_new/v_new [B,S,K,h] are its
    keys; the prompt's history (tokens < off) lives in arena blocks
    [N,K,bs,h] mapped by tables [B,nb]. Queries attend resident history
    slots plus causal in-chunk keys, optionally under the sink+window
    sparse mask (mask_window=0 → dense causal). Non-resident table entries
    alias the null block and are masked by off. Quantized arenas (the
    scale-plane kwargs) dequantize only the gathered HISTORY tiles — the
    chunk's in-flight k_new/v_new stay f32. The Pallas kernel
    (kernels/paged_prefill.py) additionally skips compute for blocks past
    the residency — this fallback pays the full gather.
    """
    B, S, H, h = q.shape
    K = k_new.shape[2]
    G = H // K
    nb = tables.shape[1]
    bs = k_pages.shape[-2]
    L = nb * bs
    f32 = jnp.float32
    off = jnp.broadcast_to(jnp.asarray(off, jnp.int32), (B,))
    cl = jnp.broadcast_to(jnp.asarray(chunk_len, jnp.int32), (B,))
    if k_scale is not None:
        k_hist = dequant_gather(k_pages, k_scale, k_tok, tables, layer=layer)
        v_hist = dequant_gather(v_pages, v_scale, v_tok, tables, layer=layer)
    else:
        ix = _at(layer, tables)
        k_hist = k_pages[ix].transpose(0, 1, 3, 2, 4).reshape(B, L, K, h)
        v_hist = v_pages[ix].transpose(0, 1, 3, 2, 4).reshape(B, L, K, h)
    pos = off[:, None] + jnp.arange(S)[None]                 # [B, S] q pos
    tok = jnp.concatenate(
        [jnp.broadcast_to(jnp.arange(L)[None], (B, L)), pos], axis=1)
    res = jnp.concatenate([jnp.arange(L)[None] < off[:, None],
                           jnp.arange(S)[None] < cl[:, None]], axis=1)

    ok = tok[:, None, :] <= pos[:, :, None]
    if mask_window > 0:
        win = (pos[:, :, None] - tok[:, None, :]) < mask_window
        if mask_sink > 0:
            win |= (tok < mask_sink)[:, None, :]
        ok &= win
    mask = res[:, None, :] & ok                              # [B, S, L+S]

    qg = q.reshape(B, S, K, G, h).astype(f32)
    k_all = jnp.concatenate([k_hist, k_new], axis=1).astype(f32)
    v_all = jnp.concatenate([v_hist, v_new], axis=1).astype(f32)
    s = jnp.einsum("bskgh,btkh->bskgt", qg, k_all) * (h ** -0.5)
    s = jnp.where(mask[:, :, None, None, :], s, jnp.asarray(NEG_INF, f32))
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bskgt,btkh->bskgh", p, v_all)
    return out.reshape(B, S, H, h).astype(q.dtype)


def paged_prefill_write(k_pages, v_pages, k_new, v_new, tables, off,
                        chunk_len, *, layer=None):
    """Scatter one chunk's K/V [B,S,K,h] (B == 1) into arena blocks.

    Chunk token i lands at absolute position off + i → physical block
    tables[0, (off+i)//bs] at in-block offset (off+i) % bs; padded tail
    rows (i >= chunk_len) are redirected to the null block 0, where
    clobbering is harmless (null contents are masked everywhere).
    """
    B, S, K, h = k_new.shape
    bs = k_pages.shape[-2]
    nb = tables.shape[1]
    pos = jnp.asarray(off, jnp.int32) + jnp.arange(S)
    valid = jnp.arange(S) < jnp.asarray(chunk_len, jnp.int32)
    blk = jnp.where(valid, tables[0, jnp.clip(pos // bs, 0, nb - 1)], 0)
    offi = pos % bs
    ix = _at(layer, blk[:, None], jnp.arange(K)[None, :], offi[:, None])
    k_pages = k_pages.at[ix].set(k_new[0].astype(k_pages.dtype))
    v_pages = v_pages.at[ix].set(v_new[0].astype(v_pages.dtype))
    return k_pages, v_pages


def update_block_summaries(kmin, kmax, kmean, k_pages, blocks, *,
                           k_scale=None, k_tok=None, layer=None):
    """Recompute the per-block key summaries for the (just-written) blocks.

    kmin/kmax/kmean [N, K, h] float32 side arrays of a [N, K, bs, h] key
    arena (with a leading n_rep axis on both for the scan-stacked period
    arenas, which take `layer`: see `_at`); blocks [M] physical block
    ids — duplicates are
    fine (every duplicate recomputes the same value from the same updated
    arena content, so scatter order does not matter). Summaries are exact
    whole-block reductions: unwritten slots hold zeros, which only widen
    the [kmin, kmax] interval, so the Quest upper bound stays valid for
    partially filled blocks (and the null block 0, a frequent redirect
    target, is harmlessly re-summarized). Quantized arenas pass the scale
    plane (k_scale/k_tok): summaries reduce the DEQUANTIZED content, so
    kmin/kmax keep bounding exactly what attention will read and the Quest
    bound stays valid with zero quant-specific scoring code. This is the
    ONLY reduction implementing the summary semantics — every write site
    (prefill chunk, decode append, dense-scatter admission) must go
    through it so the zero-stale-summary (and zero-stale-scale) invariant
    cannot diverge between paths.
    """
    ix = _at(layer, jnp.asarray(blocks, jnp.int32))
    k = k_pages[ix].astype(jnp.float32)                  # [R?, M, K, bs, h]
    if k_scale is not None:
        k = k * quant_effective_scale(k_scale[ix], k_tok[ix])
    return (kmin.at[ix].set(k.min(axis=-2)),
            kmax.at[ix].set(k.max(axis=-2)),
            kmean.at[ix].set(k.mean(axis=-2)))


def block_topk_scores(q, kmin, kmax, tables, lens, *, block_size,
                      layer=None):
    """Quest-style upper-bound block scores (pure-jnp path).

    q [B, H, h]; kmin/kmax [N, K, h]; tables [B, nb] physical block ids;
    lens [B] resident logical slots → scores [B, nb] f32: the channel-wise
    upper bound on any key dot-product inside the block, maxed over (kv
    head, query head); NEG_INF for blocks whose logical slot range starts
    at or past lens (their table entries alias the null block). The Pallas
    kernel (kernels/block_topk.py) DMAs only the tabled [K, h] summary
    tiles — this fallback pays the full gather.
    """
    B, H, h = q.shape
    K = kmin.shape[-2]
    G = H // K
    nb = tables.shape[1]
    ix = _at(layer, tables)
    lo = kmin[ix].astype(jnp.float32)                    # [B, nb, K, h]
    hi = kmax[ix].astype(jnp.float32)
    qg = q.reshape(B, K, G, h).astype(jnp.float32)[:, None]
    ub = jnp.maximum(qg * lo[:, :, :, None, :],
                     qg * hi[:, :, :, None, :]).sum(-1)  # [B, nb, K, G]
    s = ub.max(axis=(2, 3))
    resident = (jnp.arange(nb)[None] * block_size) < lens[:, None]
    return jnp.where(resident, s, NEG_INF)


def select_kv_blocks(scores, tables, lens, *, block_size, k_static,
                     frac=0.0, sink_blocks=1, recent_blocks=2):
    """Per-slot top-k block selection → a COMPACTED block table.

    scores [B, nb] upper-bound block scores (NEG_INF past residency);
    tables [B, nb]; lens [B] resident logical slots. Selects up to
    `k_static` resident blocks per slot — sink blocks (logical j <
    sink_blocks) and the most recent `recent_blocks` (always including the
    partial tail) are force-kept, the rest ranked by score. With `frac > 0`
    the per-slot budget is ceil(frac · resident_blocks) (floored at the
    keeps), so the budget tracks each slot's own context; `frac == 0` uses
    the absolute `k_static`. Budgets ≥ the resident count degrade to exact
    attention: every resident block is kept in logical order and the
    output equals the input table bit-for-bit.

    Selected blocks land in the compacted table in LOGICAL ORDER (ascending
    sort), so all entries but the last are full blocks and the tail keeps
    its partial fill — `new_lens = (m-1)·bs + tail_fill` makes the
    unmodified ``paged_decode`` occupancy masking correct on the compacted
    view. Unused entries point at the null block 0.

    Returns (new_tables [B, k_static], new_lens [B], m [B] selected block
    counts, selected [B, nb] bool mask over the ORIGINAL logical blocks).
    """
    B, nb = tables.shape
    lens = jnp.asarray(lens, jnp.int32)
    n_res = (lens + block_size - 1) // block_size        # [B] ≥ 1 in decode
    j = jnp.arange(nb)
    resident = j[None] < n_res[:, None]
    keep = resident & ((j[None] < sink_blocks)
                       | (j[None] >= (n_res - recent_blocks)[:, None]))
    adj = jnp.where(keep, jnp.inf, jnp.where(resident, scores, -jnp.inf))
    _, idx = jax.lax.top_k(adj, k_static)                # [B, k_static]
    if frac > 0:
        k_b = jnp.ceil(frac * n_res).astype(jnp.int32)
        k_b = jnp.maximum(k_b, sink_blocks + recent_blocks)
    else:
        k_b = jnp.full_like(n_res, k_static)
    k_b = jnp.minimum(k_b, n_res)                        # degrade: keep all
    sel = (jnp.arange(k_static)[None] < k_b[:, None]) \
        & jnp.take_along_axis(resident, idx, 1)
    sidx = jnp.sort(jnp.where(sel, idx, nb), axis=1)     # ascending, pad→nb
    gat = jnp.take_along_axis(tables, jnp.minimum(sidx, nb - 1), 1)
    new_tables = jnp.where(sidx < nb, gat, 0)
    m = sel.sum(axis=1)
    tail_fill = lens - (n_res - 1) * block_size
    new_lens = jnp.maximum(m - 1, 0) * block_size + tail_fill
    selected = jnp.zeros((B, nb), bool) \
        .at[jnp.arange(B)[:, None], idx].set(sel)        # idx rows distinct
    return new_tables, new_lens, m, selected


def selected_attention_mass(q, k_pages, tables, lens, selected, *,
                            k_scale=None, k_tok=None, layer=None):
    """Exact attention mass the selected blocks capture, per slot.

    q [B, H, h]; k_pages [N, K, bs, h]; tables/selected [B, nb] over the
    ORIGINAL logical blocks; lens [B] resident slots. Computes the full
    resident softmax (the dense-fallback gather — this is a diagnostics
    pass, gated by `omniattn.topk_measure_mass`) and sums the probability
    landing in selected blocks, averaged over heads → [B] in [0, 1].
    Quantized arenas pass the key scale plane so the mass is measured over
    the content attention actually reads.
    """
    B, H, h = q.shape
    K, bs = k_pages.shape[-3], k_pages.shape[-2]
    G = H // K
    nb = tables.shape[1]
    if k_scale is not None:
        k_lin = dequant_gather(k_pages, k_scale, k_tok, tables, layer=layer)
    else:
        k_lin = k_pages[_at(layer, tables)].transpose(0, 1, 3, 2, 4) \
            .reshape(B, nb * bs, K, h).astype(jnp.float32)
    qg = q.reshape(B, K, G, h).astype(jnp.float32)
    s = jnp.einsum("bkgh,bwkh->bkgw", qg, k_lin) * (h ** -0.5)
    valid = jnp.arange(nb * bs)[None] < lens[:, None]
    s = jnp.where(valid[:, None, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    slot_sel = jnp.repeat(selected, bs, axis=1)          # [B, nb*bs]
    return (p * slot_sel[:, None, None, :]).sum(-1).mean(axis=(1, 2))


def paged_cache_write(k_pages, v_pages, k_new, v_new, blk, off, *,
                      layer=None):
    """Write one token's K/V per sequence into arena blocks.

    arenas [N, K, bs, h]; k_new/v_new [B, K, h]; blk/off [B] physical block
    id and in-block offset. Distinct live sequences always target distinct
    blocks (append-only block ownership); freed slots are redirected to the
    null block by the caller, where duplicate writes are harmless.
    """
    K = k_pages.shape[-3]
    ix = _at(layer, blk[:, None], jnp.arange(K)[None, :], off[:, None])
    k_pages = k_pages.at[ix].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[ix].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def paged_cache_write_tokens(k_pages, v_pages, k_new, v_new, blk, off, *,
                             layer=None):
    """Write a per-sequence token WINDOW into arena blocks.

    arenas [N, K, bs, h]; k_new/v_new [B, S, K, h] (S window rows per
    sequence); blk/off [B, S] physical block id and in-block offset per row.
    The speculative-verify commit: the caller redirects rejected/padded rows
    to the null block 0, so only the accepted prefix ever lands in a real
    block — rollback is the absence of a write, never an undo. Distinct live
    sequences own distinct blocks and a window's rows occupy distinct
    (block, offset) slots, so scatter order is irrelevant outside null.
    """
    K = k_pages.shape[-3]
    ix = _at(layer, blk[:, :, None], jnp.arange(K)[None, None, :],
             off[:, :, None])
    k_pages = k_pages.at[ix].set(k_new.astype(k_pages.dtype))
    v_pages = v_pages.at[ix].set(v_new.astype(v_pages.dtype))
    return k_pages, v_pages


def paged_cache_write_tokens_masked(k_pages, v_pages, k_new, v_new, blk, off,
                                    write, *, layer=None):
    """`paged_cache_write_tokens` for arenas WITHOUT a null block (the ring
    arenas): rows with write[b,s] False write back the slot's CURRENT
    content (gather-then-where, the `prefill_resume_attention` idiom), so a
    rejected draft row is a bit-exact no-op on its target slot. Callers must
    keep each sequence's masked-in rows on distinct (blk, off) slots."""
    K = k_pages.shape[-3]
    ix = _at(layer, blk[:, :, None], jnp.arange(K)[None, None, :],
             off[:, :, None])
    cur_k = k_pages[ix]                                  # [.., B, S, K, h]
    cur_v = v_pages[ix]
    wm = write[:, :, None, None]
    k_wr = jnp.where(wm, k_new.astype(k_pages.dtype), cur_k)
    v_wr = jnp.where(wm, v_new.astype(v_pages.dtype), cur_v)
    k_pages = k_pages.at[ix].set(k_wr)
    v_pages = v_pages.at[ix].set(v_wr)
    return k_pages, v_pages


def ring_slot(t, sink: int, recent: int):
    """Cache slot for the token written at absolute position t (sink+ring)."""
    W = sink + recent
    return jnp.where(t < W, t, sink + (t - sink) % recent)


def cache_write(k_cache, v_cache, k_new, v_new, t, *, sink: int = 0,
                recent: int = 0, layer=None):
    """Write one token's K/V at position t (scalar, or [B] per-request).
    Full cache when sink==recent==0 (slot=t), else sink+recent ring layout."""
    t = jnp.asarray(t)
    if sink or recent:
        idx = ring_slot(t, sink, recent)
    else:
        idx = t
    if t.ndim:                        # per-request write positions
        ix = _at(layer, jnp.arange(k_new.shape[0]), idx)
        k_cache = k_cache.at[ix].set(k_new)
        v_cache = v_cache.at[ix].set(v_new)
        return k_cache, v_cache
    if layer is None:
        k_cache = jax.lax.dynamic_update_slice_in_dim(
            k_cache, k_new[:, None], idx, axis=1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(
            v_cache, v_new[:, None], idx, axis=1)
        return k_cache, v_cache
    start = (layer, 0, idx, 0, 0)
    k_cache = jax.lax.dynamic_update_slice(k_cache, k_new[None, :, None],
                                           start)
    v_cache = jax.lax.dynamic_update_slice(v_cache, v_new[None, :, None],
                                           start)
    return k_cache, v_cache


def resident_token_positions(W: int, off, *, sink: int, recent: int):
    """Token position resident at each cache slot after `off` tokens written.

    Full cache (sink==recent==0): slot j holds token j iff j < off. Ring
    layout (cache_write): slots < sink are immutable sink tokens; ring slot
    j ≥ sink hosts the residue class {j, j+recent, j+2·recent, ...} and the
    resident token is the largest class member < off.

    Returns (tok_pos [W] int32, resident [W] bool).
    """
    j = jnp.arange(W, dtype=jnp.int32)
    off = jnp.asarray(off, jnp.int32)
    if sink or recent:
        wraps = jnp.maximum((off - 1 - j) // recent, 0)
        tok = jnp.where(j < sink, j, j + wraps * recent)
    else:
        tok = j
    return tok, tok < off


def spec_verify_ring_attention(q, k_new, v_new, k_cache, v_cache, positions,
                               *, sink: int, recent: int):
    """Read-only speculative-verify attention over a ring (sink+recent) cache.

    q [B,S,H,h] is each slot's draft window at absolute positions [B,S]
    (row i of slot b at positions[b, 0] + i); k_new/v_new [B,S,K,h] are the
    window's rope'd keys; caches [B,W,K,h] hold the FROZEN ring history —
    tokens < positions[:, 0], each ring slot its residue class's largest
    member below the window. Nothing is written: the accepted prefix is
    committed afterwards by `paged_cache_write_tokens_masked`.

    Mask equivalence with baseline ring decode: single-token decode writes
    position t into its ring slot and attends every occupied slot, so the
    resident set at t is exactly { tok : t - tok < recent } ∪ sink — the
    ring's physical eviction IS the window. A verify query row at position
    p = off + i must therefore drop any frozen token with p - tok ≥ recent:
    its evicting class member tok + recent lies in [off, p], i.e. it is an
    in-window key this row attends instead. In-window keys themselves are
    always within the window (i < S ≤ recent — callers keep the draft
    window no longer than the smallest ring, see `chunked_prefill_support`)
    so they take only the causal mask. Padded draft rows need no masking:
    a padded key j is attended only by query rows i ≥ j, which are
    themselves padding whose outputs the acceptance rule never reads.
    """
    B, S, H, h = q.shape
    K = k_new.shape[2]
    G = H // K
    W = k_cache.shape[1]
    scale = h ** -0.5
    f32 = jnp.float32
    pos = jnp.asarray(positions, jnp.int32)              # [B, S]
    off = pos[:, 0]
    # per-slot resident map (the [B]-batched resident_token_positions)
    j = jnp.arange(W, dtype=jnp.int32)[None]             # [1, W]
    if sink or recent:
        wraps = jnp.maximum((off[:, None] - 1 - j) // recent, 0)
        tok = jnp.where(j < sink, j, j + wraps * recent)
    else:
        tok = jnp.broadcast_to(j, (B, W))
    res = tok < off[:, None]                             # [B, W]

    def allowed(p, t):
        ok = t <= p
        if recent > 0:
            ok &= ((p - t) < recent) | (t < sink)
        return ok

    m_old = res[:, None, :] & allowed(pos[:, :, None], tok[:, None, :])
    m_new = allowed(pos[:, :, None], pos[:, None, :])    # causal in-window
    qg = q.reshape(B, S, K, G, h).astype(f32)
    s_old = jnp.einsum("bskgh,bwkh->bskgw", qg,
                       k_cache.astype(f32)) * scale
    s_old = jnp.where(m_old[:, :, None, None, :], s_old,
                      jnp.asarray(NEG_INF, f32))
    s_new = jnp.einsum("bskgh,bukh->bskgu", qg, k_new.astype(f32)) * scale
    s_new = jnp.where(m_new[:, :, None, None, :], s_new,
                      jnp.asarray(NEG_INF, f32))
    p_att = jax.nn.softmax(jnp.concatenate([s_old, s_new], axis=-1), axis=-1)
    v_all = jnp.concatenate([v_cache.astype(f32), v_new.astype(f32)], axis=1)
    out = jnp.einsum("bskgw,bwkh->bskgh", p_att, v_all)
    return out.reshape(B, S, H, h).astype(q.dtype)


def prefill_resume_attention(q, k_new, v_new, k_cache, v_cache, positions, *,
                             chunk_len, sink: int, recent: int,
                             mask_window: int = 0, mask_sink: int = 0,
                             attend_limit: int = 0, layer=None):
    """Exact continuation-prefill attention for one chunk.

    q [B,S,H,h], k_new/v_new [B,S,K,h] at absolute `positions` [S]
    (= off + arange(S)); caches [B,W,K,h] hold tokens < off. Queries attend
    resident cache tokens plus causal in-chunk keys, optionally under a
    sink+window sparsity mask (mask_window=0 → dense causal). Only the first
    `chunk_len` chunk rows are real: padded tail queries produce garbage
    outputs (callers must ignore them) and padded keys are neither attended
    nor written. The chunk is scattered into the cache at linear slots when
    sink==recent==0, else at ring slots — callers must keep S ≤ recent for
    ring caches so in-chunk slots stay distinct.

    attend_limit (static, full layout only): a known upper bound on off —
    scores are computed against cache[:, :attend_limit] instead of the whole
    allocation, so early chunks pay O(prefix), not O(max_len).

    Returns (out [B,S,H,h], k_cache', v_cache').
    """
    B, S, H, h = q.shape
    K = k_new.shape[2]
    G = H // K
    Wc = k_cache.shape[-3]
    lim = Wc
    if attend_limit and not (sink or recent):
        lim = min(attend_limit, Wc)
    att = _at(layer, slice(None), slice(0, lim))
    k_att, v_att = k_cache[att], v_cache[att]
    W = k_att.shape[1]
    scale = h ** -0.5
    f32 = jnp.float32
    pos = jnp.asarray(positions, jnp.int32)
    off = pos[0]
    cl = jnp.asarray(chunk_len, jnp.int32)
    valid_q = jnp.arange(S) < cl

    def allowed(p, t):
        ok = t <= p
        if mask_window > 0:
            ok &= ((p - t) < mask_window) | (t < mask_sink)
        return ok

    tok_old, res_old = resident_token_positions(W, off, sink=sink, recent=recent)
    qg = q.reshape(B, S, K, G, h).astype(f32)
    s_old = jnp.einsum("bskgh,bwkh->bskgw", qg, k_att.astype(f32)) * scale
    m_old = res_old[None, :] & allowed(pos[:, None], tok_old[None, :])
    s_old = jnp.where(m_old[None, :, None, None, :], s_old,
                      jnp.asarray(NEG_INF, f32))
    s_new = jnp.einsum("bskgh,bukh->bskgu", qg, k_new.astype(f32)) * scale
    m_new = allowed(pos[:, None], pos[None, :]) & valid_q[None, :]
    s_new = jnp.where(m_new[None, :, None, None, :], s_new,
                      jnp.asarray(NEG_INF, f32))

    p_att = jax.nn.softmax(jnp.concatenate([s_old, s_new], axis=-1), axis=-1)
    v_all = jnp.concatenate([v_att.astype(f32), v_new.astype(f32)], axis=1)
    out = jnp.einsum("bskgw,bwkh->bskgh", p_att, v_all)
    out = out.reshape(B, S, H, h).astype(q.dtype)

    # the chunk lands after the reads above
    slots = order_after(ring_slot(pos, sink, recent) if (sink or recent)
                        else pos, out)
    safe = jnp.clip(slots, 0, Wc - 1)
    bi = jnp.arange(B)[:, None]
    vq = valid_q[None, :, None, None]
    k_wr = jnp.where(vq, k_new.astype(k_cache.dtype),
                     k_cache[_at(layer, bi, safe[None, :])])
    v_wr = jnp.where(vq, v_new.astype(v_cache.dtype),
                     v_cache[_at(layer, bi, safe[None, :])])
    ix = _at(layer, bi, slots[None, :])
    k_cache = k_cache.at[ix].set(k_wr, mode="drop")
    v_cache = v_cache.at[ix].set(v_wr, mode="drop")
    return out, k_cache, v_cache


def compress_prefill_kv(k, v, *, sink: int, recent: int, true_len=None):
    """Build a sink+recent ring cache from full prefill K/V [B, S, K, h].

    Ring layout: token i (i ≥ sink) lives at slot sink + (i - sink) % recent,
    so after a prefill of `true_len` tokens the ring holds the latest token of
    each residue class. true_len (traced scalar) supports right-padded
    prefill; defaults to S.
    """
    B, S, K, h = k.shape
    W = sink + recent
    if true_len is None and S <= W:
        pad = W - S
        kc = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        vc = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        return kc, vc
    tl = jnp.asarray(S if true_len is None else true_len, jnp.int32)
    j = jnp.arange(recent)
    base = sink + j
    n_wraps = jnp.maximum((tl - 1 - base) // recent, 0)
    p = jnp.clip(base + n_wraps * recent, 0, S - 1)       # token at ring slot j
    valid = (base < tl).astype(k.dtype)[None, :, None, None]
    ring_k = jnp.take(k, p, axis=1) * valid
    ring_v = jnp.take(v, p, axis=1) * valid
    sink_n = min(sink, S)
    sink_k = k[:, :sink_n]
    sink_v = v[:, :sink_n]
    if sink_n < sink:
        sink_k = jnp.pad(sink_k, ((0, 0), (0, sink - sink_n), (0, 0), (0, 0)))
        sink_v = jnp.pad(sink_v, ((0, 0), (0, sink - sink_n), (0, 0), (0, 0)))
    return (jnp.concatenate([sink_k, ring_k], axis=1),
            jnp.concatenate([sink_v, ring_v], axis=1))
