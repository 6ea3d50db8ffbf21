"""Host seconds the program spent tracing, lowering and compiling programs
(compile-cache loads included) inside the window, from the program's own
compile counter (`repro.serving.spans.COMPILES`); 0.0 where nothing
compiled, None where the program has no such counter."""


def read(run):
    try:
        from repro.serving.spans import COMPILES
    except ImportError:
        return None
    return COMPILES.seconds_between(run.t0, run.t_end)
