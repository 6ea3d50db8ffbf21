"""Model FLOPs utilization of the whole step, in %: the forward-pass
operations of every token processed in the window (prefill and decode,
attention over the context included; `bench/flops.py`) over the window's
seconds times the chip's bf16 peak.

Decode: output token j of a prompt of length n attends n + j positions.
Prefill: the computed prompt tokens of the window (PrefillEngine.stats)
through the weights, and the attention of the prompts whose first token
came back in the window, counted from their first position (an upper
bound where part of a prompt was reused).
"""
from bench import flops


def read(run):
    d = run.dims
    ops = 0.0
    for tr in run.tracks:
        n = len(tr.req.prompt)
        for j in range(1, len(tr.times)):
            if run.t0 <= tr.times[j] <= run.t_end:
                ops += flops.token_flops(d, n + j)
        if tr.times and run.t0 <= tr.times[0] <= run.t_end:
            ops += flops.prompt_flops(d, 0, n) - \
                2 * flops.matmul_params(d) * n
    ops += 2 * flops.matmul_params(d) * run.prefill.get("tokens", 0)
    if ops <= 0:
        return None
    return 100.0 * ops / (run.seconds * run.peak["bf16_flops"])
