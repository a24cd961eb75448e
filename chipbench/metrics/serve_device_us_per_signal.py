"""Kernels: device seconds in the traced slice over the signal rows of
the dispatches that ran in it (all device work in a serving window is
the serving programs and their transfers), in microseconds per row."""
import serving


def read(obs):
    if obs.reduction is None or obs.reduction.busy_s <= 0:
        return None
    rows = sum(d["rows"] for d in serving.dispatches(obs, traced=True))
    return 1e6 * obs.reduction.busy_s / rows if rows else None
