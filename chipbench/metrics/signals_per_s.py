"""Graph-signal rows answered over the window, per second: every row of
every request answered by the window's end; failed or shed requests
count zero."""


def read(obs):
    if not obs.requests:
        return None
    start, end = obs.window
    rows = sum(r["rows"] for r in obs.requests
               if r["ok"] and r["t_done"] <= end)
    return rows / (end - start)
