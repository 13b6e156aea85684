"""Set-up spent compiling in the steps before the window (``jit.compile_s``:
tracing, lowering, and the backend compile or the load from the
compilation cache), in s. The adapter's probe (``leaf_norms``) compiles
inside step 0 and is counted too, as ``setup_s`` pays for it."""
from bench import spans


def read(ctx):
    run = spans.last_run(ctx)
    if run is None:
        return None
    return sum(r["value"] for r in run.counts("jit.compile_s")
               if r["step"] is not None and r["step"] < run.warm)
