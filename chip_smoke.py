#!/usr/bin/env python3
"""Bring-up smoke run of the OmniInfer serving path on TPU.

    python chip_smoke.py [--seed N]   # one chip: phases K, A, B, C, D
    python chip_smoke.py --chips 4    # four chips: the MoE expert-parallel path
    python chip_smoke.py --precision-check   # B/C in f32, highest precision

One chip, qwen2-1.5b at its published widths (28 layers, d_model 1536,
12/2 heads, head_dim 128, vocab 151936, bf16), random weights from
``PRNGKey(--seed)``:

  K  every Pallas kernel the serving path reaches against its
     ``kernels/ref.py`` oracle at the serving shapes (the correctness gate);
  A  the launcher as users start it (``repro.launch.serve.main``);
  B  ``Server`` with every layer paged, Pallas kernels and speculative
     decoding (k=4) on: chunked paged prefill, zero-copy handoff, paged
     decode and batched verify;
  C  B's requests with jnp attention and no speculation. The B/C token
     agreement is reported, not gated: random weights give nearly tied
     logits, so bf16 reduction order alone can flip a greedy choice;
  D  int8 KV arenas (QuantPlane) and online top-k block selection
     (``topk_frac=0.5``) with Pallas on, no speculation (it does not
     compose with top-k selection).

Four chips (``--chips 4``), qwen2-moe-a2.7b at published widths, every
layer paged: the same weights (cut to 4 of 24 layers, so that one chip
holds them with room for the step) served on one chip and on an ``ep=4``
expert-parallel mesh, with token agreement; then all 24 layers on
``ep=4`` with OmniPlacement on, weights born sharded, on the placement
the 4-layer servers used (a freed server's memory must come back).

``--precision-check``: B and C again with f32 weights at the highest
matmul precision, where rounding no longer flips near-tied logits; fails
unless every greedy stream of B (Pallas, speculation) equals C's.

Every phase prints one JSON line of bring-up observations (no speed
claims). A serving phase fails unless every request finished, none was
shed, errored or timed out, the KV pool and block summaries are coherent
and each decode engine fetched to host once per step. B and D also fail
unless the compiled decode and prefill hot loops hold a Mosaic kernel
(``tpu_custom_call``). The last line is
``{"ok": true, "device": {...}}``; any failure exits non-zero before it.
The script refuses to run (exit 2) where JAX finds no TPU.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

import jax

ARCH = "qwen2-1.5b"
MOE_ARCH = "qwen2-moe-a2.7b"
MOE_PARITY_LAYERS = 4     # 4 of 24 layers: 5.9 GB of bf16 weights on one chip
MOE_MAX_LEN = 512
MAX_LEN = 2048
SLOTS = 8
N_REQUESTS = 8
BLOCK = 16                # ServerConfig.kv_block_size


class SmokeFailure(RuntimeError):
    pass


def check(ok: bool, what: str):
    if not ok:
        raise SmokeFailure(what)


# ----------------------------------------------------------------------
class Recorder:
    """Prints one JSON line per phase: wall seconds, the seconds JAX spent
    tracing, lowering and compiling inside the phase and the functions that
    took most of them (the program's compile counter,
    `repro.serving.spans.COMPILES`), and the device's peak memory so far."""

    def __init__(self):
        from repro.serving.spans import COMPILES
        self.compiles = COMPILES

    def start(self) -> float:
        return time.monotonic()

    def emit(self, phase: str, started: float, **fields):
        now = time.monotonic()
        stats = jax.devices()[0].memory_stats() or {}
        rec = {"phase": phase, "wall_s": now - started,
               "compile_s": self.compiles.seconds_between(started, now),
               "compiled": self.compiles.by_fun_between(started, now),
               "peak_bytes_in_use": stats.get("peak_bytes_in_use"), **fields}
        print(json.dumps(rec, default=str), flush=True)


# ----------------------------------------------------------------------
# phase K: kernels vs oracles at serving shapes
# Tolerances of phase K, per kernel: (rtol, atol) on |kernel - oracle|.
# Inputs are bf16 and both sides accumulate in f32; the oracle runs at
# full f32 precision. The attention kernels round their output to bf16
# (<= 2^-9 relative: rtol 1e-2 covers it with margin) and feed f32
# probabilities to the probability x value matmul through one bf16 MXU
# pass at default precision (<= 2^-9 relative per term, a few 1e-3
# absolute at most: atol). Largest errors seen on a TPU v5e, over two
# sets of random inputs: paged_decode 2.0e-3, paged_decode_int8 3.9e-3,
# paged_prefill 9.8e-4, paged_prefill_int8 2.0e-3, spec_verify(_int8)
# 3.9e-3, sink_decode 3.9e-3, flash_prefill 1.6e-2 at |o| in [2, 4) (one
# bf16 ulp there, so rtol carries it); each atol is 2.5 to 5 times its
# kernel's. Masked-out keys hold POISON values (see _kernel_cases), so a
# kernel that attends one position it should not is off by about
# POISON / history >= 0.5, far outside every tolerance. block_topk sums
# f32 products on the vector unit: only the summation order differs from
# the oracle.
TOLERANCE = {
    "paged_decode": (1e-2, 5e-3),
    "paged_decode_int8": (1e-2, 1e-2),
    "paged_prefill": (1e-2, 5e-3),
    "paged_prefill_int8": (1e-2, 5e-3),
    "spec_verify": (1e-2, 1e-2),
    "spec_verify_int8": (1e-2, 1e-2),
    "sink_decode": (1e-2, 1e-2),
    "flash_prefill": (1e-2, 1e-2),
    "block_topk": (1e-5, 1e-3),
}
POISON = 1000.0           # value rows at positions no query may attend


def _kernel_cases(key, K, G, h):
    """(name, kernel thunk, oracle thunk) at serving shapes: bf16
    activations and arenas as the engines hold them, int8 arenas with their
    f32 scale plane for the QuantPlane variants. Every position a query
    must not see (past a slot's length, the null block 0, padded chunk and
    window rows, ring slots past occupancy) holds POISON values, or
    127 x POISON / 100 after int8 dequantization."""
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ref
    from repro.kernels.block_topk import block_topk_scores
    from repro.kernels.flash_prefill import flash_prefill
    from repro.kernels.paged_decode import paged_decode
    from repro.kernels.paged_prefill import paged_prefill
    from repro.kernels.sink_decode import sink_decode
    from repro.kernels.spec_verify import spec_verify

    bs, slots, max_len = BLOCK, SLOTS, MAX_LEN
    nb = max_len // bs
    n_blocks = slots * nb + 1            # + the null block 0
    rng = np.random.default_rng(0)
    ks = iter(jax.random.split(key, 32))
    bf16 = jnp.bfloat16

    def normal(shape, dtype=bf16):
        return jax.random.normal(next(ks), shape, jnp.float32).astype(dtype)

    # every slot owns a disjoint random set of blocks; entries past its
    # residency point at the null block, as the engine leaves them. Slot 0
    # holds 1000 tokens: the history of the prefill chunk below.
    perm = rng.permutation(np.arange(1, n_blocks)).reshape(slots, nb)
    lens = rng.integers(1, max_len + 1, slots)
    lens[0] = 1000
    live = np.arange(nb)[None] * bs < lens[:, None]
    tables = jnp.asarray(np.where(live, perm, 0), jnp.int32)
    # hidden[n, j]: arena position (block n, offset j) lies past its slot's
    # length, or in the null block
    pos = np.zeros((n_blocks, bs), np.int64)
    pos[perm] = (np.arange(nb)[:, None] * bs + np.arange(bs)[None])[None]
    owner_len = np.zeros(n_blocks, np.int64)
    owner_len[perm] = lens[:, None]
    hidden = pos >= owner_len[:, None]
    hidden[0] = True
    hid = jnp.asarray(hidden)[:, None, :, None]           # [N, 1, bs, 1]
    lens = jnp.asarray(lens, jnp.int32)

    kp = normal((n_blocks, K, bs, h))
    vp = jnp.where(hid, POISON, normal((n_blocks, K, bs, h))).astype(bf16)
    # int8 arenas: blocks holding a hidden position are unsealed
    # (per-token scales, POISON / 100 on the hidden tokens); of the
    # others, half are sealed (per-channel scales, zero token scales)
    sealed = jnp.asarray((rng.random(n_blocks) < 0.5)
                         & ~hidden.any(1))[:, None, None]

    def scale_plane(poison):
        sc = jax.random.uniform(next(ks), (n_blocks, K, h), jnp.float32,
                                0.005, 0.02)
        tok = jax.random.uniform(next(ks), (n_blocks, K, bs), jnp.float32,
                                 0.005, 0.02)
        if poison:
            tok = jnp.where(hid[..., 0], POISON / 100, tok)
        return jnp.where(sealed, sc, 0.0), jnp.where(sealed, 0.0, tok)

    def int8_pages():
        return jax.random.randint(next(ks), (n_blocks, K, bs, h), -127, 128,
                                  jnp.int32).astype(jnp.int8)

    kq = int8_pages()
    vq = jnp.where(hid, 127, int8_pages()).astype(jnp.int8)
    k_scale, k_tok = scale_plane(False)
    v_scale, v_tok = scale_plane(True)
    quant = dict(k_scale=k_scale, k_tok=k_tok, v_scale=v_scale, v_tok=v_tok)

    def poison_rows(x, n_real):
        """x [B, K, S, h] with rows >= n_real [B] set to POISON."""
        rows = jnp.arange(x.shape[2])[None, None, :, None]
        return jnp.where(rows >= jnp.asarray(n_real)[:, None, None, None],
                         POISON, x).astype(x.dtype)

    q_dec = normal((slots, K, G, h))
    # one prefill chunk (64 rows, 50 real) over slot 0's 1000 tokens
    S, off, cl = 64, 1000, 50
    t1 = tables[:1]
    q_pf, kn_pf = normal((1, K, S * G, h)), normal((1, K, S, h))
    vn_pf = poison_rows(normal((1, K, S, h)), jnp.asarray([cl]))
    # verify: k+1 = 5 window rows per slot after its whole history, the
    # first n_tok of them real
    SV = 5
    n_tok = jnp.asarray(rng.integers(1, SV + 1, slots), jnp.int32)
    q_v, kn_v = normal((slots, K, SV * G, h)), normal((slots, K, SV, h))
    vn_v = poison_rows(normal((slots, K, SV, h)), n_tok)
    lo = normal((n_blocks, K, h), jnp.float32)
    hi = lo + jnp.abs(normal((n_blocks, K, h), jnp.float32))
    # dense attention: one 1024-token prompt, kv heads repeated to H
    q_fp, k_fp, v_fp = (normal((K * G, 1024, h)) for _ in range(3))
    # ring caches of W slots, POISON past each slot's occupancy
    W = 1024
    occ = jnp.asarray(rng.integers(1, W + 1, slots), jnp.int32)
    kc, vc = normal((slots, K, W, h)), poison_rows(normal((slots, K, W, h)),
                                                   occ)

    return [
        ("paged_decode",
         lambda: paged_decode(q_dec, kp, vp, tables, lens),
         lambda: ref.paged_decode_ref(q_dec, kp, vp, tables, lens)),
        ("paged_decode_int8",
         lambda: paged_decode(q_dec, kq, vq, tables, lens, **quant),
         lambda: ref.paged_decode_ref(q_dec, kq, vq, tables, lens, **quant)),
        ("paged_prefill",
         lambda: paged_prefill(q_pf, kn_pf, vn_pf, kp, vp, t1, off, cl),
         lambda: ref.paged_prefill_ref(q_pf, kn_pf, vn_pf, kp, vp, t1, off,
                                       cl)),
        ("paged_prefill_int8",
         lambda: paged_prefill(q_pf, kn_pf, vn_pf, kq, vq, t1, off, cl,
                               **quant),
         lambda: ref.paged_prefill_ref(q_pf, kn_pf, vn_pf, kq, vq, t1, off,
                                       cl, **quant)),
        ("spec_verify",
         lambda: spec_verify(q_v, kn_v, vn_v, kp, vp, tables, lens, n_tok),
         lambda: ref.spec_verify_ref(q_v, kn_v, vn_v, kp, vp, tables, lens,
                                     n_tok)),
        ("spec_verify_int8",
         lambda: spec_verify(q_v, kn_v, vn_v, kq, vq, tables, lens, n_tok,
                             **quant),
         lambda: ref.spec_verify_ref(q_v, kn_v, vn_v, kq, vq, tables, lens,
                                     n_tok, **quant)),
        ("block_topk",
         lambda: block_topk_scores(q_dec, lo, hi, tables, lens,
                                   block_size=bs),
         lambda: ref.block_topk_scores_ref(q_dec, lo, hi, tables, lens,
                                           block_size=bs)),
        ("flash_prefill",
         lambda: flash_prefill(q_fp, k_fp, v_fp),
         lambda: ref.flash_prefill_ref(q_fp, k_fp, v_fp)),
        ("sink_decode",
         lambda: sink_decode(q_dec, kc, vc, occ),
         lambda: ref.sink_decode_ref(q_dec, kc, vc, occ)),
    ]


def phase_kernels(rec: Recorder, seed: int, K: int, G: int, h: int):
    """Every kernel vs its oracle at the served model's attention widths:
    K kv heads, G query heads per kv head, head_dim h."""
    import numpy as np

    started = rec.start()
    errors = {}
    for name, kern, oracle in _kernel_cases(jax.random.PRNGKey(seed), K, G,
                                             h):
        rtol, atol = TOLERANCE[name]
        got = np.asarray(jax.block_until_ready(kern()), np.float32)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(oracle(), np.float32)
        check(got.shape == want.shape, f"{name}: shape {got.shape} "
              f"!= oracle {want.shape}")
        check(bool(np.isfinite(got).all()), f"{name}: non-finite output")
        err = np.abs(got - want)
        bad = err > atol + rtol * np.abs(want)
        errors[name] = float(err.max())
        check(not bad.any(), f"{name}: {int(bad.sum())} elements outside "
              f"rtol={rtol} atol={atol} (max abs err {err.max():.3g})")
    rec.emit("K", started, kernels=errors)


# ----------------------------------------------------------------------
# serving phases
def check_server(srv, summary, n: int):
    check(summary["n_done"] == n,
          f"{summary['n_done']}/{n} requests finished")
    for key in ("n_shed", "n_errors", "n_timeouts", "n_aborted"):
        check(summary[key] == 0, f"{key} = {summary[key]}")
    check(not summary["wall_expired"], "run stopped at its wall limit")
    if srv.kv_arena is not None:
        # pool bookkeeping + the zero-stale-summary/scale invariant
        srv.kv_arena.pool.check_invariants(srv.kv_arena)
    for i, eng in enumerate(srv.decodes):
        if srv.kv_arena is None:
            eng.pool.check_invariants()
        st = eng.stats
        check(st["host_fetches"] == st["steps"],
              f"decode {i}: {st['host_fetches']} host fetches for "
              f"{st['steps']} steps")


def kernels_in_hot_loops(srv) -> dict:
    """Compile the decode and prefill hot loops from the abstract arguments
    of their first call and look for a Mosaic kernel in each."""
    found = {}
    for role, names in (("decode", ("DecodeEngine._step_impl",
                                    "DecodeEngine._verify_impl")),
                        ("prefill", ("PrefillEngine._resume_paged_impl",))):
        called = [e for e in srv.placement.hot_loops.called()
                  if e.name in names]
        check(bool(called), f"no {role} hot loop was called")
        for e in called:
            text = e.lower().compile().as_text()
            found[e.name] = "tpu_custom_call" in text
            check(found[e.name], f"{e.name}: no tpu_custom_call in the "
                                 f"compiled hot loop")
    return found


def phase_requests(vocab: int, seed: int, lo: int, hi: int, prefix: int,
                   max_tokens: int, n: int = N_REQUESTS):
    """n greedy requests of lo..hi prompt tokens; the even ones share a
    `prefix`-token prefix."""
    import numpy as np

    from repro.core.proxy import SamplingParams
    rng = np.random.default_rng(seed)
    shared = rng.integers(0, vocab, prefix).tolist()
    reqs = []
    for i in range(n):
        if i % 2 == 0:
            tail = rng.integers(0, vocab, int(rng.integers(
                max(lo - prefix, 1), hi - prefix + 1))).tolist()
            prompt = shared + tail
        else:
            prompt = rng.integers(0, vocab, int(rng.integers(lo, hi + 1)))\
                .tolist()
        reqs.append((tuple(prompt), SamplingParams(max_tokens=max_tokens)))
    return reqs


def serve_phase(rec: Recorder, name, cfg, scfg, params, reqs, *,
                pattern=None, placement=None, want_kernels=False, **extra):
    from repro.serving import Server
    started = rec.start()
    srv = Server(cfg, scfg, pattern=pattern, params=params,
                 placement=placement)
    s = srv.run(reqs, max_wall_s=900)
    check_server(srv, s, len(reqs))
    outs = {r.rid: list(r.output_tokens) for r in srv.metrics.done}
    kern = kernels_in_hot_loops(srv) if want_kernels else None
    fields = dict(requests_done=s["n_done"],
                  prompt_tokens=sum(len(p) for p, _ in reqs),
                  output_tokens=sum(len(v) for v in outs.values()),
                  decode_steps=[e.stats["steps"] for e in srv.decodes],
                  **extra)
    if kern is not None:
        fields["tpu_custom_call"] = kern
    if scfg.spec is not None:
        fields["spec_accepted"] = s.get("spec_accepted")
    if s.get("n_migrations") is not None and cfg.moe.n_experts:
        fields["n_migrations"] = s["n_migrations"]
        fields["migration_log"] = s["migration_log"]
    del srv
    gc.collect()
    rec.emit(name, started, **fields)
    return outs


def agreement(a: dict, b: dict) -> dict:
    """Token agreement of two runs' greedy streams, per request."""
    same = total = 0
    first_div = {}
    for rid in sorted(a):
        x, y = a[rid], b.get(rid, [])
        n = max(len(x), len(y))
        eq = [i < len(x) and i < len(y) and x[i] == y[i] for i in range(n)]
        same += sum(eq)
        total += n
        if not all(eq):
            first_div[rid] = eq.index(False)
    return {"tokens_equal": same, "tokens": total,
            "requests_identical": len(a) - len(first_div),
            "first_divergence": first_div}


def phase_launcher(rec: Recorder, seed: int):
    from repro.launch import serve
    started = rec.start()
    s, srv = serve.main(["--arch", ARCH, "--max-len", str(MAX_LEN),
                         "--slots", str(SLOTS), "--requests",
                         str(N_REQUESTS), "--max-tokens", "32",
                         "--seed", str(seed)])
    check_server(srv, s, N_REQUESTS)
    out_tokens = sum(len(r.output_tokens) for r in srv.metrics.done)
    del srv
    gc.collect()
    rec.emit("A", started, requests_done=s["n_done"],
             output_tokens=out_tokens)


def paged_setup(rec: Recorder, cfg, seed: int):
    """Weights for every layer paging, and B's requests: prompts of
    256..1536 tokens, half sharing a 512-token prefix, 64 tokens each."""
    from repro.distributed.ctx import local_mesh_ctx
    from repro.models import LM
    pattern = [0] * cfg.n_layers
    started = rec.start()
    params = LM.build(cfg, local_mesh_ctx(), pattern=pattern).init(
        jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    rec.emit("weights", started, params=int(sum(
        x.size for x in jax.tree.leaves(params))),
             dtype=cfg.param_dtype)
    reqs = phase_requests(cfg.vocab_size, seed, lo=MAX_LEN // 8,
                          hi=3 * MAX_LEN // 4, prefix=MAX_LEN // 4,
                          max_tokens=64)
    return pattern, params, reqs


def serving_config(**kw):
    from repro.serving import ServerConfig
    return ServerConfig(n_prefill=1, n_decode=1, decode_slots=SLOTS,
                        max_len=MAX_LEN, kv_block_size=BLOCK, **kw)


def phases_b_c(rec: Recorder, cfg, params, reqs, pattern, suffix=""):
    """B (Pallas, speculation k=4) and C (jnp, no speculation) on the same
    weights and requests; returns their token agreement."""
    from repro.serving import SpecConfig
    out_b = serve_phase(rec, "B" + suffix, cfg.with_updates(use_pallas=True),
                        serving_config(spec=SpecConfig(k=4)), params, reqs,
                        pattern=pattern, want_kernels=True)
    out_c = serve_phase(rec, "C" + suffix, cfg, serving_config(), params,
                        reqs, pattern=pattern)
    agree = agreement(out_b, out_c)
    print(json.dumps({"phase": f"B{suffix}/C{suffix}", **agree}), flush=True)
    return agree


def run_one_chip(rec: Recorder, seed: int):
    from repro.configs import get_config
    from repro.serving.quant import QuantConfig

    cfg = get_config(ARCH)
    phase_kernels(rec, seed, cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads,
                  cfg.head_dim)
    phase_launcher(rec, seed)
    pattern, params, reqs = paged_setup(rec, cfg, seed)
    phases_b_c(rec, cfg, params, reqs, pattern)
    # no speculation here: it refuses to compose with query-dependent
    # top-k block selection (serving/spec.py)
    serve_phase(rec, "D", cfg.with_updates(use_pallas=True,
                                           omniattn_topk_frac=0.5),
                serving_config(quant=QuantConfig(bits=8)), params, reqs,
                pattern=pattern, want_kernels=True)


def run_precision_check(rec: Recorder, seed: int):
    """B and C with f32 weights and activations and every matmul at full
    f32 precision. In bf16 the two disagree on near-tied random-weight
    logits; with that rounding gone, Pallas with speculation (paged
    prefill, decode and the batched verify) must emit the same greedy
    tokens as jnp without it, or the serving path has a fault."""
    from repro.configs import get_config
    jax.config.update("jax_default_matmul_precision", "highest")
    cfg = get_config(ARCH).with_updates(compute_dtype="float32",
                                        param_dtype="float32")
    pattern, params, reqs = paged_setup(rec, cfg, seed)
    agree = phases_b_c(rec, cfg, params, reqs, pattern, suffix="-f32")
    check(agree["requests_identical"] == len(reqs),
          f"f32 at highest precision: Pallas+spec and jnp differ "
          f"({agree['tokens_equal']}/{agree['tokens']} tokens equal)")


# ----------------------------------------------------------------------
# four chips: expert-parallel serving of the MoE model
def run_four_chips(rec: Recorder, seed: int):
    from repro.configs import get_config
    from repro.core.placement import SchedulerConfig
    from repro.models import LM
    from repro.serving import DevicePlacement, ServerConfig

    check(len(jax.devices()) >= 4,
          f"--chips 4 needs 4 devices, JAX sees {len(jax.devices())}")
    full = get_config(MOE_ARCH)
    # cut to MOE_PARITY_LAYERS layers: the one-chip side of the pair must
    # hold every expert of every layer (24 layers: ~28.6 GB of bf16; 8
    # layers, 10.4 GB, left too little of 16 GB for the decode step)
    cut = full.with_updates(n_layers=MOE_PARITY_LAYERS)
    reqs = phase_requests(full.vocab_size, seed, lo=MOE_MAX_LEN // 16,
                          hi=MOE_MAX_LEN // 2, prefix=MOE_MAX_LEN // 8,
                          max_tokens=32)
    # every layer pages: OmniAttn's sink+recent rings hold 4224 slots per
    # decode slot whatever max_len is, replicated on every chip at tp=1

    def scfg(placement: bool):
        pcfg = SchedulerConfig(b_trigger=1.01, delta=0.0, window=2,
                               ema_alpha=1.0, budget=0) if placement else None
        return ServerConfig(n_prefill=1, n_decode=1, decode_slots=SLOTS,
                            max_len=MOE_MAX_LEN, kv_block_size=BLOCK,
                            enable_placement=placement,
                            placement_interval=4, placement_cfg=pcfg)

    # one ep=4 placement for every server on the mesh, as a deployment
    # keeps its mesh: each server's memory returns when it is freed
    ep4 = DevicePlacement.build(tp=1, ep=4)
    paged = [0] * cut.n_layers
    lm1 = LM.build(cut, DevicePlacement.local().ctx, pattern=paged)
    params1 = lm1.init(jax.random.PRNGKey(seed))
    out1 = serve_phase(rec, "P1", cut, scfg(False), params1, reqs,
                       pattern=paged, placement=DevicePlacement.local(),
                       layers=MOE_PARITY_LAYERS, mesh="1 chip")
    params4 = ep4.transfer_params(lm1, params1,
                                  LM.build(cut, ep4.ctx, pattern=paged))
    del params1
    gc.collect()
    out4 = serve_phase(rec, "P4", cut, scfg(False), params4, reqs,
                       pattern=paged, placement=ep4,
                       layers=MOE_PARITY_LAYERS, mesh="ep=4")
    del params4
    gc.collect()
    print(json.dumps({"phase": "P1/P4", **agreement(out1, out4)}),
          flush=True)
    # all 24 layers, born sharded on the ep=4 mesh (LM.init's jit has
    # out_shardings), live expert placement on
    pattern = [0] * full.n_layers
    params = LM.build(full, ep4.ctx, pattern=pattern).init(
        jax.random.PRNGKey(seed))
    serve_phase(rec, "F4", full, scfg(True), params, reqs, pattern=pattern,
                placement=ep4, layers=full.n_layers, mesh="ep=4")


# ----------------------------------------------------------------------
def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--precision-check", action="store_true",
                    help="one chip, only B and C in f32 at highest matmul "
                         "precision, gated on identical greedy streams")
    args = ap.parse_args(argv)

    backend = jax.default_backend()
    if backend != "tpu":
        print(f"chip_smoke: JAX found no TPU (default backend: {backend})",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))
    from repro.launch.cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    rec = Recorder()
    dev = jax.devices()[0]
    print(json.dumps({"phase": "start", "compile_cache": cache_dir,
                      "jax": jax.__version__}), flush=True)
    try:
        if args.chips == 4:
            run_four_chips(rec, args.seed)
        elif args.precision_check:
            run_precision_check(rec, args.seed)
        else:
            run_one_chip(rec, args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
