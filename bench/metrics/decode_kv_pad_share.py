"""Share of the KV slots the decode programs read that hold no live
context: 1 - (the rows' live context tokens) / (batch bucket x page
bucket x page size), summed over the decode steps of the traced stretch,
from the program's ``decode_kv_live`` / ``decode_kv_slots``."""


def read(ctx):
    c = ctx["counters"]
    if c.get("decode_kv_slots", 0) <= 0:
        return None
    return (1.0 - c["decode_kv_live"] / c["decode_kv_slots"]) * 100.0
