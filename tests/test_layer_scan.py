"""The layer scan carries its caches.

`stack_apply` threads every input cache through the scan's carry, stacked
[n_rep, ...], and each layer reads and writes only its own rows of it by
the scan counter. These tests hold it to a plain Python loop of
`apply_layer` over unstacked per-layer slices — no scan, no stacking — on
random arenas: outputs, caches and online-sparsity stats must agree bit
for bit in decode and paged prefill, with bf16 and int8 (QuantPlane)
arenas, block summaries maintained, top-k block selection, and a stack
whose period mixes full layers with sliding-window ring layers."""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import reduced_config
from repro.distributed.ctx import local_mesh_ctx
from repro.models import stack as stack_mod
from repro.models.stack import StackPlan, apply_layer, stack_apply

N_BLOCKS, BS, MAX_LEN, SLOTS, CHUNK = 29, 8, 64, 3, 16


def _cfg(stack, topk):
    cfg = reduced_config("qwen2-1.5b").with_updates(
        compute_dtype="float32", param_dtype="float32", n_layers=4)
    if stack == "mixed":
        # period (sliding-window ring layer, full layer) x 2
        cfg = cfg.with_updates(local_per_global=1, local_window=16,
                               prefill_sparse=True)
    if topk:
        cfg = cfg.with_updates(omniattn=dataclasses.replace(
            cfg.omniattn, topk_blocks=3, topk_measure_mass=True))
    return cfg


def _randomize(tree, key):
    """Every leaf random: payloads, summaries and scale planes alike (a
    zero seal scale row reads as an unsealed block)."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))

    def one(a, k):
        if a.dtype == jnp.int8:
            return jax.random.randint(k, a.shape, -127, 128, jnp.int32) \
                .astype(jnp.int8)
        if a.dtype == jnp.int32:
            return a
        r = jax.random.normal(k, a.shape, jnp.float32)
        if a.ndim >= 3 and a.dtype == jnp.float32:
            r = jnp.where(r > 0.5, 0.0, jnp.abs(r) * 0.02)   # scales
        return r.astype(a.dtype)
    return jax.tree.unflatten(treedef, [one(a, k)
                                        for a, k in zip(leaves, keys)])


def _inputs(cfg, mesh, plan, mode, quant, key):
    """(caches, x, positions, block_tables, extra stack_apply kwargs)."""
    kd, kx, kt = jax.random.split(key, 3)
    arena = stack_mod.alloc_arena_kv(cfg, mesh, plan, N_BLOCKS, BS,
                                     dtype=jnp.bfloat16, quant=quant)
    nb = MAX_LEN // BS
    if mode == "decode":
        B = SLOTS
        private = stack_mod.alloc_paged_private_cache(
            cfg, mesh, plan, B, MAX_LEN, BS, dtype=jnp.bfloat16)
        pos = jnp.asarray([[5], [17], [40]], jnp.int32)
        kw = {"token_mask": jnp.asarray([True, True, False])}
        S = 1
    else:
        B = 1
        private = stack_mod.alloc_prefill_private_cache(
            cfg, mesh, plan, MAX_LEN, dtype=jnp.bfloat16)
        pos = 21 + jnp.arange(CHUNK, dtype=jnp.int32)
        kw = {"true_len": jnp.int32(CHUNK - 3)}
        S = CHUNK
    caches = _randomize(stack_mod.merge_arena_cache(cfg, plan, private,
                                                    arena), kd)
    tables = 1 + jax.random.permutation(kt, N_BLOCKS - 1)[:B * nb] \
        .reshape(B, nb).astype(jnp.int32)
    x = jax.random.normal(kx, (B, S, cfg.d_model), jnp.float32)
    return caches, x, pos, tables, kw


def _loop_reference(cfg, mesh, plan, params, x, caches, **kw):
    """The stack as a Python loop of apply_layer over one layer's slices."""
    layer = lambda tree, r: jax.tree.map(lambda a: a[r], tree)  # noqa: E731
    new, sps = [[] for _ in plan.period], []
    for r in range(plan.n_rep):
        for i, spec in enumerate(plan.period):
            x, nc, _, sp = apply_layer(
                cfg, mesh, spec, layer(params["period"][i], r), x,
                cache=layer(caches["period"][i], r), batch_part=None, **kw)
            new[i].append(nc)
            sps += [] if sp is None else [sp]
    rem = []
    for i, spec in enumerate(plan.rem):
        x, nc, _, sp = apply_layer(cfg, mesh, spec, params["rem"][i], x,
                                   cache=caches["rem"][i], batch_part=None,
                                   **kw)
        rem.append(nc)
        sps += [] if sp is None else [sp]
    period = tuple(jax.tree.map(lambda *a: jnp.stack(a), *n) for n in new)
    return x, {"period": period, "rem": tuple(rem)}, sps


def _carried(cfg, mesh, plan, params, x, caches, **kw):
    x, new, aux = stack_apply(cfg, mesh, plan, params, x, caches=caches,
                              **kw)
    sps = [s for r in range(plan.n_rep)
           for s in (a[r] for a in aux["period_sparsity"])] \
        + list(aux["rem_sparsity"])
    return x, {"period": new["period"], "rem": new["rem"]}, sps


@pytest.mark.parametrize("mode", ["decode", "prefill"])
@pytest.mark.parametrize("arena", ["bf16", "int8"])
@pytest.mark.parametrize("stack", ["full", "mixed"])
def test_carried_scan_matches_layer_loop(mode, arena, stack):
    from repro.models import LM
    mesh = local_mesh_ctx()
    cfg = _cfg(stack, topk=(mode == "decode" and stack == "full"))
    pattern = [0] * cfg.n_layers
    lm = LM.build(cfg, mesh, pattern=pattern)
    plan = StackPlan.from_config(cfg, pattern)
    assert plan.n_rep > 1
    if stack == "mixed":
        assert {s.window > 0 for s in plan.period} == {True, False}
    params = lm.init(jax.random.PRNGKey(0))["stack"]
    caches, x, pos, tables, kw = _inputs(cfg, mesh, plan, mode,
                                         arena == "int8",
                                         jax.random.PRNGKey(1))
    kw.update(mode=mode, positions=pos, max_len=MAX_LEN,
              block_tables=tables)
    got = jax.jit(functools.partial(_carried, cfg, mesh, plan, **kw))(
        params, x, caches)
    want = jax.jit(functools.partial(_loop_reference, cfg, mesh, plan,
                                     **kw))(params, x, caches)
    assert len(got[2]) == len(want[2])
    if cfg.omniattn.topk_blocks:
        assert got[2]
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        np.testing.assert_array_equal(np.asarray(g), np.asarray(w))
    # the caches really were written: the carried stack is not its input
    assert any(not np.array_equal(np.asarray(a), np.asarray(b)) for a, b in
               zip(jax.tree.leaves(got[1]["period"]),
                   jax.tree.leaves(caches["period"])))
