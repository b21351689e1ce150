"""Seconds from the start of the run to the opening of the window: start-up,
weights made on the device, the engine built, every step width compiled
or loaded from the compile cache, and the traffic's ramp."""


def read(obs):
    return obs.setup_s
