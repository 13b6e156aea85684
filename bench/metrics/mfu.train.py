"""Model FLOPs utilisation of the training window: model FLOPs per token
(``bench/flops.py``; rematerialised FLOPs do not count) times the tokens per
second completed in the traced window, over chips times the bf16 peak."""
from bench import flops


def read(ctx):
    facts, c = ctx.outcome.facts, ctx.cell.config
    if not facts.get("tokens") or not facts.get("window_s"):
        return None
    per_token = flops.dense_lm_train_flops_per_token(
        c["hidden_size"], c["num_hidden_layers"], c["num_attention_heads"],
        c["num_key_value_heads"], c["head_dim"], c["intermediate_size"],
        c["vocab_size"], facts["seq"])
    rate = facts["tokens"] / facts["window_s"]
    peak = flops.peaks(ctx.device_kind)["bf16_flops_per_s"]
    return 100.0 * per_token * rate / (ctx.chips * peak)
