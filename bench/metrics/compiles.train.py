"""Backend compiles (``jit.compiles``, a load from the compilation cache
included) made during the window's steps: 0 when every shape was warmed."""
from bench import spans


def read(ctx):
    run = spans.last_run(ctx)
    if run is None:
        return None
    window = set(run.window)
    return float(sum(r["value"] for r in run.counts("jit.compiles") if r["step"] in window))
