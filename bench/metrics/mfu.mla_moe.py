"""Model FLOPs utilisation of the latent-attention MoE training window:
model FLOPs per token (``bench/flops_mla_moe.py``; rematerialised FLOPs do
not count) times the tokens per second completed in the traced window,
over chips times the bf16 peak."""
from bench import flops, flops_mla_moe


def read(ctx):
    facts = ctx.outcome.facts
    if not facts.get("tokens") or not facts.get("window_s"):
        return None
    per_token = flops_mla_moe.train_flops_per_token(ctx.cell.config, facts["seq"])
    rate = facts["tokens"] / facts["window_s"]
    peak = flops.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * per_token * rate / (ctx.chips * peak)
