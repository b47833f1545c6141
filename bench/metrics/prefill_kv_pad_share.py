"""Share of the KV slots the ragged prefill reads that no token attends
to: 1 - (sum over real tokens of position + 1) / (sum over dispatches of
token bucket x page bucket x page size) over the traced stretch, from the
program's ``prefill_kv_live`` / ``prefill_kv_slots``. Every packed token
gathers its sequence's whole padded page run."""


def read(ctx):
    c = ctx["counters"]
    if c.get("prefill_kv_slots", 0) <= 0:
        return None
    return (1.0 - c["prefill_kv_live"] / c["prefill_kv_slots"]) * 100.0
