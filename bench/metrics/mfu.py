"""Model FLOPs of the whole serving step as a share of the chip's bf16
peak over the traced stretch: every prompt token prefilled at its own
position and every decode row at its sequence's live context, 2 FLOPs per
matrix parameter, attention by context, the LM head for decode rows."""


def read(ctx):
    c, cf, cost = ctx["counters"], ctx["config"], ctx["costs"]
    flops = 0.0
    if c["prefill_tokens"] > 0:
        flops += c["prefill_tokens"] * cost.token_flops(
            cf, c["prefill_pos"] / c["prefill_tokens"], False)
    if c["decode_rows"] > 0:
        flops += c["decode_rows"] * cost.token_flops(
            cf, c["decode_ctx"] / c["decode_rows"], True)
    window = ctx["trace"]["window_s"]
    if flops <= 0 or window <= 0:
        return None
    return flops / (window * ctx["peaks"]["bf16_flops_per_s"]) * 100.0
