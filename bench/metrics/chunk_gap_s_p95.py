"""95th percentile (nearest rank) of the gaps between consecutive
``eval_fn`` returns in the window, the first measured from its start:
the host clock's view of one chunk, stalls included."""
import math


def read(ctx):
    ends = [ctx.t_window[0]] + list(ctx.chunk_ends)
    gaps = sorted(b - a for a, b in zip(ends, ends[1:]))
    if len(gaps) < 2:
        return None
    return gaps[math.ceil(0.95 * len(gaps)) - 1]
