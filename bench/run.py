#!/usr/bin/env python3
"""Run one benchmark cell once, on the chip.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up makes the weights from `--seed` on the device, builds the server
from the cell's configuration file, compiles every shape the window will
use (from the compile cache at a fixed path in the checkout, or
`$JAX_COMPILATION_CACHE_DIR`), and brings the server to the steady state
the mix states (slots filled, a queue behind them, or the mix itself run
for a while). The window then offers the cell's traffic open-loop for
`--seconds` through `Server.add_request` / `Server.step`.
After it, the peak device memory is read, the server is freed, and a
sample of the requests it finished is compared with the plain float32
reference (`bench/correct.py`).

The last line of standard output is one JSON object: `correct`,
`attempted`, `failed`, `metrics` (the cell's end-to-end metrics, or with
`--trace 1` its per-layer metrics), `device`, with `--trace 1` a
`breakdown`, and last `checks`, each compared number beside its limit.
The checks are also the last lines of standard error. Exits 2, printing no
result, where JAX finds no TPU or fewer chips than the cell asks for.
"""
import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CACHE_DIR = ROOT / ".jax_cache"
TRACE_DIR = ROOT / ".bench_trace"


def log(*a):
    print(*a, file=sys.stderr, flush=True)


def compile_cache(jax) -> str:
    """Persistent compilation cache: `$JAX_COMPILATION_CACHE_DIR` where set
    (JAX reads it itself), else a fixed directory in the checkout (the path
    is part of the cache key, so it must not move)."""
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(CACHE_DIR)


def run(cell, seed: int, seconds: float, traced: bool, t_process: float,
        rate: float = None, platform: str = "tpu",
        control: bool = False) -> dict:
    import jax

    from bench import correct, driver, measure, program, traffic
    from bench import trace as trace_mod
    from bench.peaks import peaks

    dev = jax.devices()[0]
    peak = peaks(dev.device_kind) if platform == "tpu" else {}
    ref = cell.reference()
    cfg = cell.config
    dims = ref.dims(cfg)
    compiles = driver.Compiles()

    phase = {}
    t = time.monotonic()
    srv = program.build_server(cfg["program"], ref.weights(cfg, seed))
    phase["build_s"] = time.monotonic() - t
    spec = dict(cell.traffic)
    if rate is not None:
        spec["rate_per_s"] = rate
    mix = traffic.Mix(spec, vocab=dims["V"])
    scfg = srv.scfg
    slots = scfg.n_decode * scfg.decode_slots
    loop = driver.Loop(srv)
    t = time.monotonic()
    driver.warm(loop, mix, scfg.max_len, scfg.kv_block_size,
                srv.prefills[0].chunk, seed)
    phase["warm_s"] = time.monotonic() - t
    fill = [driver.Track(r, loop.clock())
            for r in mix.fill(slots, seed) + mix.backlog(seed)]
    for tr in fill:
        loop.submit(tr, loop.clock())
    while any(tr.first is None and tr.reason is None for tr in fill):
        loop.step()
    if mix.preroll_s:
        fill += loop.window(mix.window(mix.preroll_s, seed, salt=3),
                            mix.preroll_s)[0]
    phase["fill_s"] = time.monotonic() - t - phase["warm_s"]
    reqs = mix.window(seconds, seed)
    pre = ([dict(e.stats) for e in srv.prefills],
           [dict(e.stats) for e in srv.decodes])
    if traced:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(TRACE_DIR), profiler_options=opts)
    setup_s = time.monotonic() - t_process
    compiles.on = True
    with jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN):
        due, t0, t_end = loop.window(reqs, seconds)
    compiles.on = False
    if traced:
        jax.profiler.stop_trace()
    post = ([dict(e.stats) for e in srv.prefills],
            [dict(e.stats) for e in srv.decodes])
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    tracks = fill + due
    rec = measure.Run(tracks=tracks, due=due, t0=t0, t_end=t_end, dims=dims,
                      slots=slots,
                      prefill=measure.stats_delta(pre[0], post[0]),
                      decode=measure.stats_delta(pre[1], post[1]), peak=peak)
    failed = sum(1 for tr in due if tr.reason not in (None, "stop", "length"))
    late = max((tr.submitted - tr.due for tr in due
                if tr.submitted is not None), default=0.0)
    log(json.dumps({"setup": {**phase, "setup_s": setup_s},
                    "window": {"due": len(due), "fill": len(fill),
                               "first_tokens": sum(tr.first is not None
                                                   for tr in due),
                               "finished": len(correct.finished(
                                   tracks, t_end)),
                               "generator_late_max_s": late,
                               "ttft_s": rec.ttft_summary(),
                               "compiles": compiles.n,
                               "compile_s": compiles.seconds},
                    "prefill": rec.prefill, "decode": rec.decode}))
    metrics, breakdown = {}, None
    if traced:
        red = trace_mod.reduce(trace_mod.load(
            trace_mod.find_xplane(str(TRACE_DIR))))
        rec.trace = red
        breakdown = {"device_ops": red.device_ops,
                     "idle_gaps": red.idle_gaps}
        for m in cell.per_layer:
            v = cell.reader(m["name"]).read(rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    else:
        for m in cell.end_to_end:
            if m["name"] == "setup_s":
                v = setup_s
            else:
                v = measure.END_TO_END[m["name"]](rec)
            if v is not None:
                metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    picked = correct.sample(tracks, t_end, seed)
    n_devices = len(jax.devices())
    del srv, loop, rec
    gc.collect()
    t = time.monotonic()
    w = ref.weights(cfg, seed)
    reading = correct.gaps(ref, cfg, w, picked)
    log(json.dumps({"reference_s": time.monotonic() - t,
                    "compared": reading["per_request"]}))
    checks = correct.checks(cell.limits, reading, failed)
    out = {"correct": all(correct.holds(c) for c in checks.values()),
           "attempted": len(due), "failed": failed, "metrics": metrics,
           "device": {"platform": dev.platform, "kind": dev.device_kind,
                      "count": n_devices, "memory_peak_bytes": mem}}
    if traced:
        out["device"].update(busy_s=red.busy_s, window_s=red.window_s)
        out["breakdown"] = breakdown
    if control:
        ctrl = correct.gaps(ref, cfg, w, picked, control=True)
        ctrl["checks"] = correct.checks(cell.limits, ctrl, failed)
        ctrl["correct"] = all(correct.holds(c)
                              for c in ctrl["checks"].values())
        out["control"] = ctrl
    out["checks"] = checks
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None,
                    help="offer this many requests/s instead of the mix's "
                         "own rate (for finding a cell's knee; never set "
                         "for a benchmark run)")
    args = ap.parse_args(argv)

    import jax
    from bench import cells
    cell = cells.load(args.workload)
    if jax.default_backend() != "tpu":
        log(f"bench: JAX found no TPU (backend {jax.default_backend()})")
        return 2
    if len(jax.devices()) < cell.chips:
        log(f"bench: {args.workload} needs {cell.chips} chips, JAX sees "
            f"{len(jax.devices())}")
        return 2
    log(json.dumps({"compile_cache": compile_cache(jax),
                    "jax": jax.__version__}))
    out = run(cell, args.seed, args.seconds, bool(args.trace), T_PROCESS,
              rate=args.rate)
    for name, c in out["checks"].items():
        log(f"check {name}: {c['value']} {c['holds']} {c['limit']}")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
