"""Front door: requests answered per coalesced dispatch over the window
(the service's ``served`` over ``dispatches`` counters)."""


def read(obs):
    dispatches = obs.stats.get("dispatches", 0)
    return obs.stats["served"] / dispatches if dispatches else None
