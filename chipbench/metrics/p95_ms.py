"""95th percentile (nearest rank) of client-side latency, from
``submit`` to the resolved future, over every request submitted in the
window; a failed request counts as beyond any limit."""
import math


def read(obs):
    if not obs.requests:
        return None
    lat = sorted((r["t_done"] - r["t_submit"]) if r["ok"] else math.inf
                 for r in obs.requests)
    value = lat[math.ceil(0.95 * len(lat)) - 1] * 1e3
    return value if math.isfinite(value) else 1e30
