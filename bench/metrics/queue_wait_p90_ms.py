"""Scheduler: 90th percentile of the wait from a request's due time to the
start of the step that admitted it, over the requests due in the window."""

from bench import stats


def read(obs):
    p = stats.percentile(stats.queue_wait_s(obs.record), 90)
    return None if p is None else 1e3 * p
