"""The control: the reference computed with float8 matmul operands, put in
the server's place, reads as not correct where the server reads as
correct. At a size a test run holds (an untied Qwen2 of width 256 and a
16384-token vocabulary, whose logits are near-tied as the published
model's are), over three seeds, through the whole run."""
import json
import time

from bench import cells
from bench import run as bench_run
from bench.cells import ROOT

FIXTURES = ROOT / "bench" / "tests" / "fixtures"


def test_float8_control_fails_where_the_server_passes():
    cfg = json.loads((FIXTURES / "small-qwen2.json").read_text())
    mix = json.loads((FIXTURES / "tiny-chat.json").read_text())
    cell = cells.Cell(name="small-qwen2.tiny-chat", chips=1,
                      config=cfg, traffic=mix,
                      limits={"max_logit_gap": 0.02}, end_to_end=[],
                      per_layer=[], root=ROOT)
    sound, control = [], []
    for seed in (1, 2, 3):
        out = bench_run.run(cell, seed, 2.0, False, time.monotonic(),
                            platform="cpu", control=True)
        sound.append(out["checks"]["max_logit_gap"]["value"])
        control.append(out["control"]["max_logit_gap"])
        assert out["correct"], out["checks"]
        assert not out["control"]["correct"], out["control"]["checks"]
        assert out["control"]["tokens"] >= 50
    assert min(control) >= 3 * max(sound)
    assert max(sound) < cell.limits["max_logit_gap"] < min(control)
