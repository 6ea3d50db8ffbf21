"""Share of the traced window in which no operation ran on the device:
1 - busy / window from the profiler trace (bench/trace.py), in %."""


def read(run):
    if run.trace is None or run.trace.window_s <= 0:
        return None
    return 100.0 * run.trace.idle_share
