"""Set-up spent in ``train.init`` (parameters, optimizer state, their
shardings) of the last training run, in s."""
from bench import spans


def read(ctx):
    run = spans.last_run(ctx)
    if run is None:
        return None
    (init,) = run.spans("train.init")
    return init["t1"] - init["t0"]
