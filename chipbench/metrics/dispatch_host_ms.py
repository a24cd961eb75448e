"""Router and engine: the dispatcher's own host time per dispatch over
the traced slice: the mean, over the ``serve.dispatch`` spans whose
middle lies in the slice, of each span's duration less its
``serve.device`` child (the wait for the device).  The stages' means
go to standard error.  A program without the spans gives none."""
import dispatch_spans
import harness


def read(obs):
    lo, hi = obs.trace_window
    if hi <= lo:
        return None
    spans = dispatch_spans.dispatches(dispatch_spans.program_tracer(),
                                      lo, hi)
    if not spans:
        return None
    host = [d["dur"] - d["stages"].get("serve.device", 0.0)
            for d in spans]
    names = sorted({k for d in spans for k in d["stages"]})
    means = {k: round(1e3 * sum(d["stages"].get(k, 0.0) for d in spans)
                      / len(spans), 4) for k in names}
    harness.log(f"dispatch_host_ms: {len(spans)} dispatches, stage means "
                f"(ms) {means}")
    return 1e3 * sum(host) / len(host)
