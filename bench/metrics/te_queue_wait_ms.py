"""TE queue wait: the 90th percentile, over the window's requests with a
first token, of (first prefill dispatch - arrival), from the program's
request timelines (``Completion.first_dispatch``)."""
import stats


def read(ctx):
    tl = ctx.get("timelines")
    if not tl:
        return None
    return stats.percentile([fd - a for a, fd, _ in tl], 90) * 1e3
