"""Seconds from a Laplacian to a served basis: the window over the
whole onboards in it (router construction plus the first answer)."""


def read(obs):
    if not obs.onboards:
        return None
    return obs.window_s / len(obs.onboards)
