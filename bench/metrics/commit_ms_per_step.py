"""Engine host loop: mean over the traced engine steps of the program's
``engine.commit`` span (verify and pick on the host, cursor advance,
prefix-cache insert, finishing, the step's bookkeeping), in ms, on the
profiler's clock."""

from bench import phases


def read(obs):
    return phases.mean_ms(obs, "engine.commit")
