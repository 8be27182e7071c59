"""mfu.train: model FLOPs per token x train_tokens_per_s of this run, over
the chips' bf16 peak (bench/peaks.json), in percent."""
from bench import flops


def read(ctx):
    win = ctx.window
    rate = win["end_to_end"].get("train_tokens_per_s")
    if not rate:
        return None
    conf = ctx.cell.config
    fpt = flops.per_token(conf, conf["train"]["seq_len"], training=True)
    return 100.0 * fpt * rate / (ctx.chips * ctx.peaks["bf16_flops_per_s"])
