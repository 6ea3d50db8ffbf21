#!/usr/bin/env python3
"""Readings that a cell's correctness limit is set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> --seeds 1 2 3 ...

For each seed, in one process: a run of the cell as `bench/run.py` makes it
(the sound reading: the widest reference logit gap of the tokens the server
served), and on the same sample the control (the reference computed with
float8 matmul operands put in the server's place, scored the same way and
held to the cell's limits: `control_correct` has to come out false).
Prints one JSON line per seed and a summary: the largest sound reading and
the smallest control reading. Benchmark runs never run this.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path[:0] = [str(Path(__file__).resolve().parent.parent)]

from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--no-control", action="store_true",
                    help="sound readings only")
    args = ap.parse_args(argv)
    import jax
    from bench import cells
    cell = cells.load(args.workload)
    if jax.default_backend() != "tpu":
        bench_run.log("control: JAX found no TPU")
        return 2
    bench_run.compile_cache(jax)
    sound, ctrl, ctrl_correct = [], [], []
    for seed in args.seeds:
        out = bench_run.run(cell, seed, args.seconds, False,
                            time.monotonic(), control=not args.no_control)
        rec = {"seed": seed, "correct": out["correct"],
               "sound": out["checks"]["max_logit_gap"]["value"],
               "metrics": {k: v["value"] for k, v in out["metrics"].items()},
               "memory_peak_bytes": out["device"]["memory_peak_bytes"]}
        sound.append(rec["sound"])
        if "control" in out:
            rec["control"] = out["control"]["max_logit_gap"]
            rec["control_correct"] = out["control"]["correct"]
            rec["control_per_request"] = out["control"]["per_request"]
            ctrl.append(rec["control"])
            ctrl_correct.append(rec["control_correct"])
        print(json.dumps(rec), flush=True)
    print(json.dumps({"workload": args.workload, "lower": max(sound),
                      "upper": min(ctrl) if ctrl else None,
                      "sound": sound, "control": ctrl,
                      "control_correct": ctrl_correct}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
