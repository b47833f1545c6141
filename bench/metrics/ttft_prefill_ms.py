"""The prefill part of TTFT: the 90th percentile, over the window's
requests with a first token, of (first token - first prefill dispatch),
from the program's request timelines (``Completion.first_dispatch``)."""
import stats


def read(ctx):
    tl = ctx.get("timelines")
    if not tl:
        return None
    return stats.percentile([ft - fd for _, fd, ft in tl], 90) * 1e3
