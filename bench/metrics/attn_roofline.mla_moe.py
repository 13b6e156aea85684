"""Share of the roofline the splash-attention kernels reach in the traced
window at latent attention's widths (query/key 192, value 128): the least
time their work could take on this chip (``flops_mla_moe.attention_cost``
of every layer of every window step, bounded by the bf16 peak or by HBM)
over the kernels' device time inside the window (the adapter's pass over
the trace), in percent."""
from bench import flops, flops_mla_moe, spans


def read(ctx):
    run = spans.last_run(ctx)
    seconds = ctx.outcome.facts.get("kernel_s", {}).get("attention")
    if run is None or not seconds:
        return None
    c, t = ctx.cell.config, ctx.cell.traffic
    cost = flops_mla_moe.attention_cost(
        t["batch"], c["num_attention_heads"], t["seq"],
        c["qk_nope_head_dim"] + c["qk_rope_head_dim"], c["v_head_dim"])
    per_step = c["num_hidden_layers"] * flops_mla_moe.roofline_s(
        cost, flops.peaks(ctx.device_kind))
    return 100.0 * len(run.window) * per_step / seconds
