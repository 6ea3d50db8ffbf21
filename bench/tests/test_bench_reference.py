"""The plain float32 reference against the serving path, at reduced size:
logits of the program's prefill, then of its decode through the cache, and
the greedy tokens the paged server streams, including a resume through a
reused prefix. Both sides in float32 here, so they agree to rounding."""
import dataclasses
import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import program
from bench.cells import ROOT
from bench.references import qwen2

FIXTURES = ROOT / "bench" / "tests" / "fixtures"
TINY = ["tiny-qwen2.json", "small-qwen2.json"]


def f32_setup(name, seed=3):
    c = json.loads((FIXTURES / name).read_text())
    cfg = program.model_config(c["program"]).with_updates(
        param_dtype="float32", compute_dtype="float32")
    w = qwen2.weights(c, seed)
    return c, cfg, w, jax.tree.map(lambda a: a.astype(jnp.float32), w)


@pytest.mark.parametrize("name", TINY)
def test_prefill_then_decode_match_reference(name):
    from repro.distributed.ctx import local_mesh_ctx
    from repro.models import LM
    c, cfg, w, w32 = f32_setup(name)
    lm = LM.build(cfg, local_mesh_ctx(), pattern=[0] * cfg.n_layers)
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab_size, 40).astype(np.int32)
    want = qwen2.logits(c, w, toks)
    with jax.default_matmul_precision("highest"):
        cache, last, _ = lm.prefill(w32, {"tokens": jnp.asarray(toks[None,
                                                                     :30])},
                                    max_len=64, tables=lm.default_tables())
        got = [np.asarray(last[0])]
        for p in range(30, 39):
            cache, lg, _ = lm.decode(w32, cache,
                                     jnp.asarray(toks[None, p:p + 1]),
                                     jnp.int32(p), tables=lm.default_tables())
            got.append(np.asarray(lg[0]))
    got = np.stack(got)
    scale = np.abs(want[29:39]).max()
    np.testing.assert_allclose(got, want[29:39], atol=1e-4 * scale, rtol=0)


@pytest.mark.parametrize("name", TINY)
def test_paged_server_serves_reference_argmax(name):
    """Greedy tokens of the paged server (chunked prefill, resume through a
    stored prefix, paged decode) have a reference gap of zero to rounding."""
    from repro.core.proxy import SamplingParams
    from repro.serving import Server
    c, cfg, w, w32 = f32_setup(name, seed=5)
    group = c["program"]
    scfg = dataclasses.replace(program.server_config(group), chunk_tokens=16)
    srv = Server(cfg, scfg, pattern=[0] * cfg.n_layers, params=w32)
    rng = np.random.default_rng(1)
    shared = tuple(rng.integers(0, cfg.vocab_size, 40).tolist())
    prompts = [shared + tuple(rng.integers(0, cfg.vocab_size, n).tolist())
               for n in (7, 19, 26)]
    outs = {}
    with jax.default_matmul_precision("highest"):
        for p in prompts:        # one after another: the later ones resume
            for o in srv.generate(p, SamplingParams(max_tokens=12)):
                outs.setdefault(o.rid, []).extend(o.new_tokens)
    assert srv.prefills[0].stats["reused_tokens"] > 0
    for rid, p in enumerate(prompts):
        served = outs[rid]
        seq = list(p) + served[:-1]
        tgt = np.zeros(len(seq), np.int32)
        tgt[len(p) - 1:] = served
        gap, _ = qwen2.score(c, w, seq, tgt, block=64, bucket=128)
        assert gap[len(p) - 1:].max() < 1e-3, (rid, gap[len(p) - 1:])
