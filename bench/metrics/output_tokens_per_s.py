"""Every output token returned in the window over the window's length."""

from bench import stats


def read(obs):
    return stats.window_tokens(obs.record) / (obs.record.close
                                              - obs.record.open)
