"""Host time per TE step that the device waits behind: the mean over the
traced stretch's ``te.step`` spans of their duration less their
``te.*.fetch`` spans (the waits for the device's results)."""
import program_view


def read(ctx):
    host = program_view.te_host_seconds(ctx.get("spans") or [])
    if not host:
        return None
    return sum(host) / len(host) * 1e3
