"""Kernels: the least time the chip could take for the dispatches that
ran in the traced slice (work.py: algorithmic operations over the bf16
peak or bytes over HBM bandwidth, whichever is larger) over the device
time measured there, in percent."""
import harness
import serving
import work


def read(obs):
    if obs.reduction is None or obs.reduction.busy_s <= 0 or not obs.peaks:
        return None
    flops = bytes_ = 0
    for d in serving.dispatches(obs, traced=True):
        f, b = work.dispatch_work(obs.family, d["work"])
        flops += f
        bytes_ += b
    if not flops:
        return None
    least, bound = work.least_seconds(flops, bytes_, obs.peaks)
    harness.log(f"serve_roofline: {flops:.4g} flops, {bytes_:.4g} bytes, "
                f"bound by {bound}")
    return 100.0 * least / obs.reduction.busy_s
