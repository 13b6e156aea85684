"""Time the training loop waited on the runtime's prefetch task
(``data.wait`` spans), per window step, in ms."""
from bench import spans


def read(ctx):
    run = spans.last_run(ctx)
    if run is None:
        return None
    window = set(run.window)
    waited = sum(r["t1"] - r["t0"] for r in run.spans("data.wait") if r["step"] in window)
    return 1e3 * waited / len(window)
