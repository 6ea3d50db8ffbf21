"""The harness: cells, mixes and metrics found by file name, a whole run
driven on the CPU at a tiny size (the chip check skipped), a run whose
served tokens are altered where they are produced coming out not correct,
and the command refusing to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys
import time

import pytest

from bench import cells
from bench import run as bench_run
from bench.cells import ROOT

FIXTURES = ROOT / "bench" / "tests" / "fixtures"
TINY_CELL = {"name": "tiny-qwen2.tiny-chat", "config": "tiny-qwen2",
             "traffic": "tiny-chat", "chips": 1,
             "why": "a cell added by new files alone"}


def checkout_with_new_cell(tmp_path):
    """A copy of the benchmark to which a configuration, a mix, a cell's
    limits and a per-layer metric are added as new files and entries."""
    root = tmp_path / "checkout"
    shutil.copytree(ROOT / "bench", root / "bench",
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    before = {p.relative_to(root): p.read_bytes()
              for p in (root / "bench").rglob("*") if p.is_file()}
    shutil.copy(FIXTURES / "tiny-qwen2.json",
                root / "bench" / "configs" / "tiny-qwen2.json")
    shutil.copy(FIXTURES / "tiny-chat.json",
                root / "bench" / "traffic" / "tiny-chat.json")
    (root / "bench" / "limits" / "tiny-qwen2.tiny-chat.json").write_text(
        json.dumps({"max_logit_gap": 0.05}))
    (root / "bench" / "metrics" / "decode_tokens.tiny.py").write_text(
        "def read(run):\n    return float(run.decode.get('tokens', 0))\n")
    bench["configs"].append({"name": "tiny-qwen2",
                             "source": "https://example.org/tiny",
                             "file": "bench/configs/tiny-qwen2.json",
                             "reduced": [], "why": "tiny"})
    bench["workloads"].append(TINY_CELL)
    bench["per_layer"].append({"name": "decode_tokens.tiny", "unit": "tokens",
                               "better": "higher",
                               "source": "program_counter",
                               "layer": "engines", "moves": "itl_p95_ms",
                               "workloads": [TINY_CELL["name"]]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))
    after = {p.relative_to(root): p.read_bytes()
             for p in (root / "bench").rglob("*") if p.is_file()}
    assert all(after[k] == v for k, v in before.items())
    return root


def test_new_cell_mix_and_metric_found_by_name(tmp_path):
    root = checkout_with_new_cell(tmp_path)
    cell = cells.load(TINY_CELL["name"], root=root)
    assert cell.config["hidden_size"] == 64
    assert cell.traffic["rate_per_s"] == 6.0
    assert cell.limits == {"max_logit_gap": 0.05}
    names = [m["name"] for m in cell.per_layer]
    assert "decode_tokens.tiny" in names
    assert "decode_step_ms" in names and "device_idle_share" in names
    assert "prefix_reuse_share" not in names      # lists other cells
    assert [m["name"] for m in cell.end_to_end] == ["itl_p95_ms", "setup_s"]
    reader = cell.reader("decode_tokens.tiny")
    assert reader.read(type("R", (), {"decode": {"tokens": 7}})) == 7.0
    assert cell.reference().dims(cell.config)["D"] == 64


def test_every_listed_cell_and_metric_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = cells.load(w["name"])
        assert cell.limits["max_logit_gap"] > 0
        for m in cell.per_layer:
            assert callable(cell.reader(m["name"]).read)


def tiny_run(tmp_path, seed=7, seconds=2.0):
    root = checkout_with_new_cell(tmp_path)
    cell = cells.load(TINY_CELL["name"], root=root)
    return bench_run.run(cell, seed, seconds, False, time.monotonic(),
                         platform="cpu")


def test_tiny_run_is_correct(tmp_path):
    out = tiny_run(tmp_path)
    assert out["correct"], out["checks"]
    assert out["attempted"] == 12 and out["failed"] == 0
    assert set(out["metrics"]) == {"itl_p95_ms", "setup_s"}
    assert list(out)[-1] == "checks"
    assert out["checks"]["requests_compared"]["value"] >= 4


def test_altered_token_is_not_correct(tmp_path, monkeypatch):
    """A token altered where the decode step produces it."""
    from repro.serving.decode import DecodeEngine
    step = DecodeEngine._step_base

    def altered(self):
        out = step(self)
        for rid in list(out)[:1]:
            out[rid] = (out[rid] + 1) % self.lm.cfg.vocab_size
        return out

    monkeypatch.setattr(DecodeEngine, "_step_base", altered)
    out = tiny_run(tmp_path)
    assert not out["correct"]
    gap = out["checks"]["max_logit_gap"]
    assert gap["value"] > gap["limit"]


def test_refuses_without_a_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, str(ROOT / "bench" / "run.py"),
                        "--workload", "qwen2-1.5b.chat-prefix", "--seed", "1",
                        "--seconds", "1", "--trace", "0"],
                       capture_output=True, text=True, env=env, timeout=120)
    assert p.returncode == 2
    assert p.stdout == ""
    assert "no TPU" in p.stderr


def test_window_opens_on_stored_prefixes():
    """After the warm-up every request of the window resumes through its
    system prompt: no shared prefix is prefilled from cold."""
    from bench import driver, program, traffic
    cfg = json.loads((FIXTURES / "tiny-qwen2.json").read_text())
    spec = json.loads((FIXTURES / "tiny-chat.json").read_text())
    spec["shared_prefix"]["groups"] = 4
    ref = cells.Cell(name="t", chips=1, config=cfg, traffic=spec, limits={},
                     end_to_end=[], per_layer=[], root=ROOT).reference()
    srv = program.build_server(cfg["program"], ref.weights(cfg, 3))
    mix = traffic.Mix(spec, vocab=cfg["vocab_size"])
    loop = driver.Loop(srv)
    driver.warm(loop, mix, srv.scfg.max_len, srv.scfg.kv_block_size,
                srv.prefills[0].chunk, 3)
    before = dict(srv.prefills[0].stats)
    due, _, _ = loop.window(mix.window(2.0, 3), 2.0)
    loop.until_idle()
    hits = srv.prefills[0].stats["prefix_hits"] - before["prefix_hits"]
    assert hits == len(due) == 12
    reused = srv.prefills[0].stats["reused_tokens"] - before["reused_tokens"]
    assert reused == sum(tr.req.prefix_len for tr in due)


def test_tiny_saturated_run_opens_full(tmp_path):
    """Slots filled and a queue behind them at set-up: correct, and every
    slot busy through the window."""
    root = checkout_with_new_cell(tmp_path)
    cell = cells.load(TINY_CELL["name"], root=root)
    cell.traffic = {"rate_per_s": 1.0,
                    "prompt": {"median": 24, "sigma": 0.5, "min": 8,
                               "max": 64},
                    "output": {"median": 48, "sigma": 0.3, "min": 24,
                               "max": 96},
                    "fill_slots_at_setup": True, "backlog_at_setup": 3}
    cell.end_to_end = [{"name": "output_tok_s", "unit": "tokens/s"}]
    cell.per_layer = []
    out = bench_run.run(cell, 2**31 + 3, 2.0, False, time.monotonic(),
                        platform="cpu")
    assert out["correct"], out["checks"]
    assert out["metrics"]["output_tok_s"]["value"] > 0
