"""Device: share of the traced steps' time in which no operation ran on the
device, in percent.  Time between steps, waiting for arrivals, does not
count; the idle share of the whole traced window is ``window_s`` less
``busy_s`` in the result's ``device``."""


def read(obs):
    spans, busy = obs.step_spans(), obs.step_busy_ns()
    total = sum(e - s for s, e in spans)
    if not total:
        return None
    return 100.0 * (1.0 - sum(busy) / total)
