"""Model step: the FLOPs the window's real tokens need (bench/cost.py) over
the window's summed step time times the chip's bf16 peak, in percent."""

from bench import cost


def read(obs):
    if obs.peaks is None:
        return None
    steps = obs.window_steps()
    flops = sum(cost.model_flops(obs.shape, s.rows, s.emitted) for s in steps)
    wall = sum(s.t1 - s.t0 for s in steps)
    if not wall:
        return None
    return 100.0 * flops / (wall * obs.peaks["flops_bf16"])
