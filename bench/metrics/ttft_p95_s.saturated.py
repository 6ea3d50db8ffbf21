"""95th percentile of time to first token over every request due in the
window, a request with no first token by the window's end counting at its
wait so far (bench/measure.py). Above the knee the queue grows all run, so
this swings with the smallest change: it is read here, and judged nowhere."""


def read(run):
    return run.ttft_p95_s()
