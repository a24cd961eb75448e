"""The front door's dispatches, read from the program's own span ring
after a run.

The program's dispatcher records one ``serve.dispatch`` span per
coalesced dispatch (args: tier, bucket width ``w``, batch rows ``b``,
``r_pad``, requests, rows, ``signal_elements``, ``block_elements``)
with its stage spans (``serve.build``, ``serve.put``, ``serve.launch``,
``serve.device``, ``serve.pull``, ``serve.reply``) nested in it on the
dispatcher's thread, into ``repro.obs.default_tracer()``.  The tracer's
clock is ``time.monotonic`` and the harness's ``now()`` is
``time.perf_counter``: on Linux both read CLOCK_MONOTONIC, so the
window's bounds select spans directly.  A program that records no such
span gives an empty list.  A ring that has dropped spans of the window
(it holds ``repro.obs.trace.DEFAULT_CAPACITY`` spans; a dispatch records
7 and a request 4) raises ``RingOverrun``: a reading over part of the
window would pass for the whole.
"""
from __future__ import annotations

import bisect
from typing import List

DISPATCH = "serve.dispatch"
PREFIX = "serve."


class RingOverrun(RuntimeError):
    """The program's span ring no longer holds the start of the window."""


def program_tracer():
    from repro import obs
    return obs.default_tracer()


def dispatches(tracer, lo: float, hi: float) -> List[dict]:
    """The dispatch spans whose middle lies in [lo, hi], oldest first:
    [{"ts", "dur", "args", "stages": {child name: seconds}}].  Raises
    ``RingOverrun`` when the ring is full and its oldest span is younger
    than ``lo``: spans of the window fell off its back."""
    spans = tracer.spans()
    if (spans and len(spans) >= tracer.capacity
            and spans[0]["ts"] > lo):
        raise RingOverrun(
            f"the span ring ({tracer.capacity} spans) starts "
            f"{spans[0]['ts'] - lo:.3f}s after the window it is read over")
    children = {}
    for s in spans:
        if s["name"].startswith(PREFIX) and s["name"] != DISPATCH:
            children.setdefault(s["tid"], []).append(s)
    starts = {}
    for tid, kids in children.items():
        kids.sort(key=lambda s: s["ts"])
        starts[tid] = [s["ts"] for s in kids]
    out = []
    for s in spans:
        if s["name"] != DISPATCH or not lo <= s["ts"] + s["dur"] / 2 <= hi:
            continue
        end = s["ts"] + s["dur"]
        kids = children.get(s["tid"], [])
        stages = {}
        for k in kids[bisect.bisect_left(starts.get(s["tid"], []),
                                         s["ts"]):]:
            if k["ts"] >= end:
                break
            if k["ts"] + k["dur"] <= end:
                stages[k["name"]] = stages.get(k["name"], 0.0) + k["dur"]
        out.append({"ts": s["ts"], "dur": s["dur"], "args": s["args"],
                    "stages": stages})
    out.sort(key=lambda d: d["ts"])
    return out
