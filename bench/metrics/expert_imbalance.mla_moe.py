"""Load of the busiest held expert against the mean held expert: for each
window step and MoE layer, the largest held expert's rows over the rows the
held experts computed divided by their number (the program's ``moe.rows``
counter), averaged. 1 is an even load; the held experts' count is the
ceiling."""
from bench import spans


def read(ctx):
    run = spans.last_run(ctx)
    if run is None:
        return None
    window = set(run.window)
    held = ctx.cell.config["n_routed_experts"]
    loads = [r["args"]["largest"] * held / r["value"]
             for r in run.counts("moe.rows") if r["step"] in window and r["value"]]
    return sum(loads) / len(loads) if loads else None
