"""Scheduler's width ladder: real tokens fed over flat positions compiled,
summed over the window's steps, in percent (``Engine.stats()["flat"]``
read when the window opens and when it closes)."""


def _sums(stats):
    f = stats.get("flat")
    if not f:
        return None
    return f["mean_tokens"] * f["steps"], f["mean_width"] * f["steps"]


def read(obs):
    a, b = _sums(obs.record.stats_open), _sums(obs.record.stats_close)
    if a is None or b is None or b[1] <= a[1]:
        return None
    return 100.0 * (b[0] - a[0]) / (b[1] - a[1])
