"""The compiled decode step's share of its roofline, in %.

Least time of each decode step in the window, the larger of its operations
over the bf16 peak and its bytes over the HBM bandwidth (`bench/flops.py`:
every weight once, the resident keys and values of each live slot, the new
ones written), summed and divided by the device time of the decode-step
program in the trace (`DecodeEngine._step_impl`). A step's live slots and
their contexts come from the tokens it returned: output token j of a
prompt of length n came from a step whose query attends n + j positions.
"""
from collections import defaultdict

from bench import flops

PROGRAM = r"_step_impl"


def read(run):
    if run.trace is None:
        return None
    dev = run.trace.program(PROGRAM)
    if dev is None or dev[0] <= 0:
        return None
    steps = defaultdict(lambda: [0, 0])          # time -> [batch, keys]
    for tr in run.tracks:
        n = len(tr.req.prompt)
        for j in range(1, len(tr.times)):
            t = tr.times[j]
            if run.t0 <= t <= run.t_end:
                steps[t][0] += 1
                steps[t][1] += n + j
    least = 0.0
    for batch, keys in steps.values():
        ops, nbytes = flops.decode_step(run.dims, batch, keys)
        least += max(ops / run.peak["bf16_flops"],
                     nbytes / run.peak["hbm_bytes_s"])
    return 100.0 * least / dev[0] if least > 0 else None
