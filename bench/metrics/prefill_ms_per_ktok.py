"""Device time of the ragged-prefill programs per 1000 prompt tokens
prefilled over the traced stretch."""


def read(ctx):
    tokens = ctx["counters"]["prefilled"]
    t = ctx["trace"]["program_s"].get("ragged_prefill", 0.0)
    if tokens <= 0 or t <= 0:
        return None
    return t / tokens * 1e6
