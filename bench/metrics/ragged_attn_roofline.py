"""Ragged-attention kernel: least time of the work its rows need
(bench/cost.py: the larger of FLOPs over the bf16 peak and bytes over HBM
bandwidth) over the kernel's device time, summed over the traced steps, in
percent.  The kernel's events are the flat step's Pallas calls: the trace
names each device operation by its HLO instruction, and the Pallas call is
the step's only ``custom_call_target="tpu_custom_call"``."""

from bench import cost


def is_kernel(name: str) -> bool:
    return 'custom_call_target="tpu_custom_call"' in name


def read(obs):
    if obs.peaks is None or obs.trace is None:
        return None
    spans = obs.step_spans()
    kernel_ns = obs.device_time_in(spans, is_kernel)
    if not kernel_ns:
        return None
    least = sum(cost.attention_least_s(obs.shape, s.rows, obs.peaks)
                for s in obs.traced_steps())
    return 100.0 * least / (kernel_ns * 1e-9)
