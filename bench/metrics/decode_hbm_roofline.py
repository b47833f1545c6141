"""Share of the HBM roofline the decode programs reach: the least bytes
one decode step must read (every layer's matrices, the LM head, and the
live KV of the batch as it stood at each step, from the configuration's
shapes) at the chip's peak bandwidth, over the measured device time per
decode step."""


def read(ctx):
    c = ctx["counters"]
    prog = ctx["trace"]["program_s"]
    t = prog.get("fused_decode", 0.0) + prog.get("legacy_decode", 0.0)
    if c["decode_steps"] <= 0 or c["decode_rows"] <= 0 or t <= 0:
        return None
    least = ctx["costs"].decode_step_bytes(
        ctx["config"], c["decode_rows"] / c["decode_steps"],
        c["decode_ctx"] / c["decode_rows"])
    step_s = t / c["decode_steps"]
    return least / ctx["peaks"]["hbm_bytes_per_s"] / step_s * 100.0
