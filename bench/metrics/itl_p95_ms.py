"""95th percentile of the gaps between consecutive output tokens of one
request, over every gap that ends in the window."""

from bench import stats


def read(obs):
    p = stats.percentile(stats.itl_s(obs.record), 95)
    return None if p is None else 1e3 * p
