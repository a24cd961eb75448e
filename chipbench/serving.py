"""Dispatches of a serving window, rebuilt from the answered requests.

The front door answers every request of one coalesced dispatch with the
same ``ServeResult.service_s`` (one host-clock span around the
dispatch), so requests that share (tier, service_s, batch_size) are one
dispatch.  Metric readers that need per-dispatch quantities use this.
"""
from __future__ import annotations

import work


def dispatches(obs, traced: bool = False) -> list:
    """[{service_s, rows, mid, work}] per dispatch; ``traced`` keeps the
    dispatches whose middle fell inside the traced slice."""
    groups = {}
    for r in obs.requests:
        if not r["ok"]:
            continue
        key = (r["tier"], r["service_s"], r["batch_size"])
        groups.setdefault(key, []).append(r)
    out = []
    for (tier, service_s, _), reqs in groups.items():
        end = min(r["t_done"] for r in reqs)
        filters = obs.bank_filters if tier == "bank" else 0
        out.append({
            "service_s": service_s,
            "rows": sum(r["rows"] for r in reqs),
            "mid": end - 0.5 * service_s,
            "work": [work.Request(graph=r["graph"], n=obs.sizes[r["graph"]],
                                  k=obs.components[r["graph"]][tier],
                                  rows=r["rows"], filters=filters)
                     for r in reqs]})
    if traced:
        lo, hi = obs.trace_window
        out = [d for d in out if lo <= d["mid"] <= hi]
    return out
