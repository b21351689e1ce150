"""Model step: mean device busy time inside the traced ``engine.step``
spans of decode-only steps (their ``engine.dispatch`` fed no prefill
token), in ms, on the profiler's clock."""

from bench import phases, xtrace


def read(obs):
    spans = [(s.start, s.end) for s in phases.decode_steps(obs)]
    if not spans:
        return None
    return sum(xtrace.busy_in(obs.trace.device, spans)) / len(spans) * 1e-6
