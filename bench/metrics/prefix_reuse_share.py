"""Share of the prompt tokens prefilled in the window that were served
from the prefix cache (resumed through a reused prefix) instead of being
computed: `reused_tokens / (reused_tokens + tokens)` of PrefillEngine.stats
over the window, in %."""


def read(run):
    reused = run.prefill.get("reused_tokens", 0)
    total = reused + run.prefill.get("tokens", 0)
    return 100.0 * reused / total if total else None
