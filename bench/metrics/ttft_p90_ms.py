"""90th percentile of time to first token over every request due in the
window, from its due time to the end of the step that returned its first
token."""

from bench import stats


def read(obs):
    p = stats.percentile(stats.ttft_s(obs.record), 90)
    return None if p is None else 1e3 * p
