"""Shape-derived operations and bytes, the peaks table, and the
configuration files' program settings against their published keys."""
import json

import pytest

from bench import flops, program
from bench.cells import ROOT
from bench.peaks import peaks
from bench.references import qwen2

CONFIGS = sorted((ROOT / "bench" / "configs").glob("*.json")) + [
    ROOT / "bench" / "tests" / "fixtures" / f"{name}.json"
    for name in ("tiny-qwen2", "small-qwen2")]


def load(path):
    return json.loads(path.read_text())


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_flops_per_token_match_active_params(path):
    """2 x the active matmul weights per token: the program's own count of
    active parameters less what a token does not multiply through (the
    embedding lookup of an untied model, norms and biases)."""
    c = load(path)
    d = qwen2.dims(c)
    cfg = program.model_config(c["program"])
    other = d["L"] * (2 * d["D"] + (d["H"] + 2 * d["K"]) * d["h"]) + d["D"]
    embed = 0 if d["tied"] else d["V"] * d["D"]
    assert flops.matmul_params(d) == cfg.n_active_params() - other - embed
    assert flops.token_flops(d, 0) == 2 * flops.matmul_params(d)
    assert flops.token_flops(d, 100) - flops.token_flops(d, 0) == \
        4 * d["L"] * d["H"] * d["h"] * 100


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_weight_bytes_match_the_program(path):
    """All weights read once: the program's parameter count in bytes, less
    the embedding table of an untied model (a lookup reads a few rows)."""
    c = load(path)
    d = qwen2.dims(c)
    cfg = program.model_config(c["program"])
    embed = 0 if d["tied"] else d["V"] * d["D"] * 2
    assert flops.weight_bytes(d) == 2 * cfg.n_params() - embed


def test_prompt_flops_add_up():
    d = qwen2.dims(load(CONFIGS[0]))
    whole = flops.prompt_flops(d, 0, 300)
    assert whole == flops.prompt_flops(d, 0, 120) + \
        flops.prompt_flops(d, 120, 300)
    assert whole == sum(flops.token_flops(d, p + 1) for p in range(300))


def test_decode_step_bytes_and_ops():
    d = qwen2.dims(load(CONFIGS[0]))
    ops, nbytes = flops.decode_step(d, 4, 1000)
    assert ops == 4 * 2 * flops.matmul_params(d) + \
        4 * d["L"] * d["H"] * d["h"] * 1000
    assert nbytes == flops.weight_bytes(d) + \
        flops.kv_bytes_per_token(d) * 1004


def test_unknown_device_kind_raises():
    assert peaks("TPU v5 lite")["bf16_flops"] == 197e12
    with pytest.raises(KeyError, match="no peaks"):
        peaks("TPU v9 imaginary")


@pytest.mark.parametrize("path", CONFIGS, ids=lambda p: p.stem)
def test_program_settings_follow_published_keys(path):
    c = load(path)
    m = c["program"]["model"]
    d = qwen2.dims(c)
    assert (m["d_model"], m["n_heads"], m["n_kv_heads"], m["head_dim"],
            m["n_layers"], m["vocab_size"], m["rms_eps"], m["rope_theta"],
            m["tie_embeddings"]) == (d["D"], d["H"], d["K"], d["h"], d["L"],
                                     d["V"], d["eps"], d["theta"], d["tied"])
    assert m["param_dtype"] == c["torch_dtype"]
    assert m["d_ff"] == c["intermediate_size"]
    for key in c["reduced"]:
        assert key in c
