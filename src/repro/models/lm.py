"""Top-level model: embeddings, modality frontends (stubs), head, losses,
and the three lowered entry points (train_loss / prefill / decode)."""
from __future__ import annotations

from dataclasses import dataclass
from functools import cached_property
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed.ctx import MeshCtx
from repro.models import moe as moe_mod
from repro.models import stack as stack_mod
from repro.models.common import ParamDef, cross_entropy, init_params, param_shapes, param_specs, rms_norm


@dataclass(frozen=True)
class LM:
    cfg: ModelConfig
    mesh: MeshCtx
    plan: stack_mod.StackPlan

    @staticmethod
    def build(cfg: ModelConfig, mesh: MeshCtx, pattern: Optional[list[int]] = None) -> "LM":
        return LM(cfg, mesh, stack_mod.StackPlan.from_config(cfg, pattern))

    # ------------------------------------------------------------------
    @cached_property
    def param_defs(self) -> dict:
        cfg = self.cfg
        fs = "data" if cfg.fsdp else None
        dt = cfg.param_dtype
        d = {"stack": stack_mod.stack_param_defs(cfg, self.mesh, self.plan),
             "final_norm": ParamDef((cfg.d_model,), P(None), dtype=dt, ones=True),
             "embed": ParamDef((cfg.vocab_size, cfg.d_model), P("model", fs),
                               scale=cfg.d_model ** -0.5, dtype=dt)}
        if not cfg.tie_embeddings:
            d["head"] = ParamDef((cfg.d_model, cfg.vocab_size), P(fs, "model"), dtype=dt)
        if cfg.frontend_dim:
            d["frontend"] = ParamDef((cfg.frontend_dim, cfg.d_model), P(None, None), dtype=dt)
        # pjit input shardings must divide exactly: drop non-divisible axes
        # (e.g. vocab 50280 or 504 on a 16-way model axis → replicate).
        d = jax.tree.map(
            lambda pd: ParamDef(pd.shape, self.mesh.sanitize_spec(pd.spec, pd.shape),
                                pd.scale, pd.dtype, pd.ones),
            d, is_leaf=lambda v: isinstance(v, ParamDef))
        return d

    def init(self, rng) -> dict:
        """Fresh params, laid out per `shardings()` (attention heads over
        `model`, MoE expert slots over `data`) so every downstream jit
        sees the canonical placement from step one.
        One jit builds them: with out_shardings each device materializes
        only its own shards, so a model larger than one device's memory
        can be born on the mesh."""
        kw = {} if self.mesh.n_devices == 1 else \
            {"out_shardings": self.shardings()}   # as in stack._alloc_placed
        return jax.jit(lambda r: init_params(self.param_defs, r), **kw)(rng)

    def specs(self) -> dict:
        return param_specs(self.param_defs)

    def shapes(self) -> dict:
        return param_shapes(self.param_defs)

    def shardings(self):
        return self.mesh.tree_shardings(self.specs())

    # ------------------------------------------------------------------
    def default_tables(self) -> Optional[dict]:
        cfg = self.cfg
        if cfg.moe.n_experts == 0:
            return None
        s = moe_mod.default_slot_count(cfg, self.mesh.ep)
        placement = moe_mod.round_robin_placement(cfg.moe.n_experts, self.mesh.ep, s)
        return moe_mod.tables_from_placement(placement, s)

    def table_specs(self) -> Optional[dict]:
        if self.cfg.moe.n_experts == 0:
            return None
        return moe_mod.table_specs()

    # ------------------------------------------------------------------
    def _embed_inputs(self, params, batch, batch_part):
        cfg = self.cfg
        cd = jnp.dtype(cfg.compute_dtype)
        if cfg.family == "audio":
            x = batch["frames"].astype(cd) @ params["frontend"]
        elif cfg.family == "vlm":
            tok = jnp.take(params["embed"], batch["tokens"], axis=0)
            patch = batch["patches"].astype(cd) @ params["frontend"]
            x = jnp.concatenate([patch, tok], axis=1)
        else:
            x = jnp.take(params["embed"], batch["tokens"], axis=0)
        x = x.astype(cd)
        return self.mesh.constrain(x, P(batch_part, None, None))

    def _logits(self, params, x):
        cfg = self.cfg
        x = rms_norm(x, params["final_norm"], cfg.rms_eps)
        cd = jnp.dtype(cfg.compute_dtype)
        if cfg.tie_embeddings:
            logits = jnp.einsum("...d,vd->...v", x.astype(cd), params["embed"])
        else:
            logits = x.astype(cd) @ params["head"]
        return logits

    # ------------------------------------------------------------------
    def train_loss(self, params, batch, tables=None):
        """batch: tokens/frames/patches + labels [B,S] (+ optional mask).
        Returns (loss, aux)."""
        cfg = self.cfg
        B = batch["labels"].shape[0]
        bp = self.mesh.batch_part(B)
        x = self._embed_inputs(params, batch, bp)
        S = x.shape[1]
        positions = jnp.arange(S)
        x, _, aux = stack_mod.stack_apply(
            cfg, self.mesh, self.plan, params["stack"], x, mode="train",
            positions=positions, batch_part=bp, tables=tables)
        logits = self._logits(params, x)
        loss = cross_entropy(logits, batch["labels"], batch.get("mask"))
        return loss, aux

    def prefill(self, params, batch, *, max_len: int, tables=None,
                true_len=None):
        """Returns (cache, last_logits [B, V]). true_len (traced scalar)
        supports right-padded prompts: the cache and last-token logits are
        computed as if the sequence were true_len long."""
        cfg = self.cfg
        key = "frames" if cfg.family == "audio" else "tokens"
        B = batch[key].shape[0]
        bp = self.mesh.batch_part(B)
        x = self._embed_inputs(params, batch, bp)
        S = x.shape[1]
        positions = jnp.arange(S)
        mode = "train" if cfg.encoder_only else "prefill"
        x, cache, aux = stack_mod.stack_apply(
            cfg, self.mesh, self.plan, params["stack"], x, mode=mode,
            positions=positions, max_len=max_len, batch_part=bp, tables=tables,
            true_len=true_len)
        if cfg.encoder_only:
            return None, self._logits(params, x), aux
        if true_len is None:
            last = x[:, -1]
        else:
            last = jax.lax.dynamic_index_in_dim(x, true_len - 1, axis=1,
                                                keepdims=False)
        logits = self._logits(params, last)
        if true_len is not None and cache is not None:
            cache["pos"] = jnp.asarray(true_len, jnp.int32)
        return cache, logits, aux

    def prefill_resume(self, params, batch, cache, *, max_len: int,
                       tables=None, chunk_len=None, attend_limit: int = 0,
                       block_tables=None):
        """Continue prefill from an existing cache (chunked prefill / radix
        prefix-KV reuse). batch['tokens'] [B,S] is the next chunk, occupying
        absolute positions cache['pos'] + arange(S); chunk_len (traced scalar)
        marks the real rows of a right-padded final chunk. Returns
        (cache, logits-of-last-real-token [B,V], aux). A prefill from scratch
        is the degenerate case: a zero cache with pos=0 (alloc_cache).
        block_tables [1, nb] (optional) selects the physically paged prefill
        path: full-attention cache leaves are block arenas, the chunk's KV
        is written straight into the tabled blocks."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        bp = self.mesh.batch_part(B)
        cd = jnp.dtype(cfg.compute_dtype)
        x = jnp.take(params["embed"], tokens, axis=0).astype(cd)
        x = self.mesh.constrain(x, P(bp, None, None))
        off = jnp.asarray(cache["pos"], jnp.int32)
        positions = off + jnp.arange(S)
        cl = jnp.asarray(S if chunk_len is None else chunk_len, jnp.int32)
        x, new_cache, aux = stack_mod.stack_apply(
            cfg, self.mesh, self.plan, params["stack"], x, mode="prefill",
            positions=positions, caches=cache, max_len=max_len,
            batch_part=bp, tables=tables, true_len=cl,
            attend_limit=attend_limit, block_tables=block_tables)
        last = jax.lax.dynamic_index_in_dim(x, cl - 1, axis=1, keepdims=False)
        logits = self._logits(params, last)
        new_cache["pos"] = off + cl
        return new_cache, logits, aux

    @cached_property
    def chunked_prefill_support(self) -> tuple:
        """(supported, max_chunk_tokens). Chunked prefill is exact only when
        every attention layer's prefill mask needs no evicted keys: full
        layers always qualify; windowed layers ride their window ring;
        compressed (OmniAttn) layers qualify only under cfg.prefill_sparse
        (dense-prefill compressed layers attend tokens the ring has dropped).
        Ring scatter-writes additionally bound the chunk to the smallest
        ring so in-chunk slots stay distinct."""
        cfg = self.cfg
        if cfg.encoder_only or cfg.family in ("vlm", "audio"):
            return False, 0
        limit = 1 << 30
        for spec in self.plan.all_specs():
            if spec.kind != "attn":
                continue
            if spec.compressed and not cfg.prefill_sparse:
                return False, 0
            sink, recent = stack_mod.cache_window(cfg, spec)
            if sink or recent:
                limit = min(limit, recent)
        return True, limit

    def decode(self, params, cache, token, positions, tables=None,
               token_mask=None, block_tables=None):
        """token [B,1] int32; positions scalar or [B,1]. → (cache, logits [B,V]).
        token_mask [B] (optional) marks live rows — it weights the MoE
        activation counts AND the online-sparsity stats (inactive slots in
        a slot-dense batch would otherwise pollute both signals).
        block_tables [B, nb] (optional) selects the physically paged decode
        path: attention cache leaves are block arenas and reads gather only
        resident blocks — and, with cfg.omniattn.topk_* set, only the
        query-selected top-k of them (aux carries the per-layer
        period_sparsity/rem_sparsity stat vectors; see serving/sparsity.py)."""
        cfg = self.cfg
        B = token.shape[0]
        bp = self.mesh.batch_part(B)
        cd = jnp.dtype(cfg.compute_dtype)
        x = jnp.take(params["embed"], token, axis=0).astype(cd)
        x = self.mesh.constrain(x, P(bp, None, None))
        x, new_cache, aux = stack_mod.stack_apply(
            cfg, self.mesh, self.plan, params["stack"], x, mode="decode",
            positions=jnp.asarray(positions), caches=cache, batch_part=bp,
            tables=tables, token_mask=token_mask, block_tables=block_tables)
        logits = self._logits(params, x[:, 0])
        return new_cache, logits, aux

    def verify(self, params, cache, tokens, positions, tables=None,
               token_mask=None, block_tables=None):
        """Speculative multi-token verify: a READ-ONLY forward over each
        slot's draft window. tokens [B, S] = [current input token,
        draft_1..draft_{S-1}] per row; positions [B] = each slot's next
        write position (the same cursor the single-token decode step
        holds). Runs the stack over all S window positions against the
        paged caches WITHOUT writing any K/V — each attention layer stages
        its rope'd window keys instead — and returns (logits [B, S, V],
        staged, aux). Greedy-prefix acceptance and the masked commit
        (`verify_commit`) happen in the caller's jit, so a rejected draft
        never touches a block or its summary. token_mask [B] marks live
        slots; it is broadcast across the window for the MoE counters
        (moe_ffn's flat [B·S] row mask)."""
        cfg = self.cfg
        B, S = tokens.shape
        bp = self.mesh.batch_part(B)
        cd = jnp.dtype(cfg.compute_dtype)
        x = jnp.take(params["embed"], tokens, axis=0).astype(cd)
        x = self.mesh.constrain(x, P(bp, None, None))
        pos2 = jnp.asarray(positions, jnp.int32)[:, None] + \
            jnp.arange(S, dtype=jnp.int32)[None]
        tm = None if token_mask is None else jnp.repeat(token_mask, S)
        x, staged, aux = stack_mod.stack_apply(
            cfg, self.mesh, self.plan, params["stack"], x, mode="verify",
            positions=pos2, caches=cache, batch_part=bp, tables=tables,
            token_mask=tm, block_tables=block_tables)
        return self._logits(params, x), staged, aux

    def verify_commit(self, staged, positions, n_write, block_tables):
        """Land the accepted prefix of a `verify` window — n_write [B] rows
        per slot — in the paged caches it read; see stack_verify_commit."""
        return stack_mod.stack_verify_commit(
            self.cfg, self.plan, staged, positions, n_write, block_tables)
