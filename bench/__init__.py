"""On-chip serving benchmark: one command runs one cell once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Everything a cell needs is found by name: `BENCHMARK.json` at the checkout
root names the cell's configuration (`bench/configs/<config>.json`), its
traffic mix (`bench/traffic/<traffic>.json`) and its per-layer metrics
(`bench/metrics/<metric>.py`); a configuration names its plain reference
(`bench/references/<reference>.py`).
"""
