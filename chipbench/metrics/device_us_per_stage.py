"""Kernels: device time a walk stage over the traced slice: the device's
busy seconds there over the ``walk_stages`` the program recorded on the
``serve.dispatch`` spans whose middle lies in the slice (each span's
stages summed over its graph blocks and both legs), in microseconds.
It reads the stage loop's cost in either family.  A program whose
spans carry no ``walk_stages`` gives none."""
import dispatch_spans


def read(obs):
    red = obs.reduction
    lo, hi = obs.trace_window
    if red is None or red.busy_s <= 0 or hi <= lo:
        return None
    spans = dispatch_spans.dispatches(dispatch_spans.program_tracer(),
                                      lo, hi)
    stages = sum((d["args"] or {}).get("walk_stages", 0) for d in spans)
    return 1e6 * red.busy_s / stages if stages else None
