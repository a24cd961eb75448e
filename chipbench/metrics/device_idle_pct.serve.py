"""Device: share of the traced serving slice in which no operation ran
on the device (1 - union of device-op intervals over the slice)."""


def read(obs):
    red = obs.reduction
    if red is None or not obs.requests or red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
