"""Scheduler: mean over the traced engine steps of the program's
``engine.schedule`` span (deadline expiry, admission, drafting, page
growth, segment planning), in ms, on the profiler's clock."""

from bench import phases


def read(obs):
    return phases.mean_ms(obs, "engine.schedule")
