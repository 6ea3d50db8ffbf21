"""Prompt tokens computed per second of prefill work: PrefillEngine.stats
`tokens / busy_s` over the window (`busy_s` is the engine's own host-clock
span around its chunks, which end in a device-to-host fetch only when a
prompt finishes)."""


def read(run):
    busy = run.prefill.get("busy_s", 0.0)
    return run.prefill.get("tokens", 0) / busy if busy > 0 else None
