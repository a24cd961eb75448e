"""Router and engine: share of the walked dispatch blocks that is
signal over the window: 100 x the signal elements (rows x graph size)
over the block elements (batch x padded rows x bucket width) that the
program recorded in the args of each ``serve.dispatch`` span of the
window, read from its span ring (the bank's filter count cancels).  A
program that records none gives none; a ring that lost the window's
start raises (``dispatch_spans.RingOverrun``)."""
import dispatch_spans


def read(obs):
    spans = dispatch_spans.dispatches(dispatch_spans.program_tracer(),
                                      *obs.window)
    if not spans:
        return None
    block = sum(d["args"]["block_elements"] for d in spans)
    signal = sum(d["args"]["signal_elements"] for d in spans)
    return 100.0 * signal / block if block else None
