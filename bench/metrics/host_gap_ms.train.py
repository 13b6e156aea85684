"""Host time per window step while the device has nothing queued: from the
end of step k-1's ``train.sync`` (the device has finished step k-1) to the
end of step k's ``train.dispatch`` (step k is queued). Logging, the
checkpoint test, the batch put and the dispatch fall in it. Mean, in ms."""
from bench import spans


def read(ctx):
    run = spans.last_run(ctx)
    if run is None:
        return None
    sync, dispatch = run.by_step("train.sync"), run.by_step("train.dispatch")
    gaps = [dispatch[k]["t1"] - sync[k - 1]["t1"] for k in run.window]
    return 1e3 * sum(gaps) / len(gaps)
