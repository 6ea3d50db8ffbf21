"""Mean decode step: DecodeEngine.stats `busy_s / steps` over the window.
Each step ends in its one device-to-host fetch, so the span covers the
device's work as well as the host's."""


def read(run):
    steps = run.decode.get("steps", 0)
    return 1e3 * run.decode["busy_s"] / steps if steps else None
