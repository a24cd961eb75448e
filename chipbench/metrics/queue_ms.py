"""Front door: mean time a request waited in the service's queue
(``ServeResult.queue_s``), over the answered requests of the window."""


def read(obs):
    waits = [r["queue_s"] for r in obs.requests if r["ok"]]
    return 1e3 * sum(waits) / len(waits) if waits else None
