"""Share of the roofline the grouped-matmul kernels reach in the traced
window: the least time their work could take on this chip (per window step
and MoE layer, ``flops_mla_moe.gmm_cost`` of the rows the program counted
in ``moe.rows``, bounded by the bf16 peak or by HBM) over the kernels'
device time inside the window (the adapter's pass over the trace), in
percent."""
from bench import flops, flops_mla_moe, spans


def read(ctx):
    run = spans.last_run(ctx)
    seconds = ctx.outcome.facts.get("kernel_s", {}).get("gmm")
    if run is None or not seconds:
        return None
    c, window = ctx.cell.config, set(run.window)
    peaks = flops.peaks(ctx.device_kind)
    bound = sum(flops_mla_moe.roofline_s(flops_mla_moe.gmm_cost(
        r["value"], c["hidden_size"], c["moe_intermediate_size"], c["n_routed_experts"]),
        peaks) for r in run.counts("moe.rows") if r["step"] in window)
    return 100.0 * bound / seconds if bound else None
