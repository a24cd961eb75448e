"""Device: share of the traced slice of a fit in which no operation ran
on the device."""


def read(obs):
    red = obs.reduction
    if red is None or not obs.onboards or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
