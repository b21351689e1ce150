"""Engine host loop: mean over the traced engine steps of the program's
``engine.logits_to_host`` span, the copy of the step's logits to the host
once the device has made them, in ms, on the profiler's clock."""

from bench import phases


def read(obs):
    return phases.mean_ms(obs, "engine.logits_to_host")
