"""Model flop utilization of serving: the flops the window's completed
requests needed (``bench/counts/lm.py``: 2 per parameter per token,
causal attention, the head where logits are needed) over the traced
window's seconds times the chip's peak bf16 flop/s, in percent."""


def read(ctx):
    flops = ctx.counts.get("model_flops")
    if not flops or ctx.trace.window_s <= 0 or ctx.trace.n_devices == 0:
        return None
    return 100.0 * flops / (ctx.trace.window_s
                            * ctx.peaks["bf16_flops_per_s"])
