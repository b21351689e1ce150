"""Engine host loop: mean over the traced steps of each ``bench.step``
span's length less the device's busy time inside it, in ms, on the
profiler's clock."""


def read(obs):
    spans, busy = obs.step_spans(), obs.step_busy_ns()
    if not spans:
        return None
    return sum((e - s) - b for (s, e), b in zip(spans, busy)) / len(spans) \
        * 1e-6
