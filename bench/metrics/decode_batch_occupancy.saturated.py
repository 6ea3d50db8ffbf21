"""Live slots per decode step over the slots there are: decode `tokens /
(steps * slots)` of DecodeEngine.stats over the window, in %."""


def read(run):
    steps = run.decode.get("steps", 0)
    if not steps:
        return None
    return 100.0 * run.decode["tokens"] / (steps * run.slots)
