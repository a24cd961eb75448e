"""Fit: ``RaggedFGFTServeEngine.onboard_seconds`` of the bucket (fit,
pack, tier spectra) over its component count, mean over the window's
onboards, in milliseconds."""


def read(obs):
    if not obs.onboards:
        return None
    per = [o["fit_s"] / o["components"] for o in obs.onboards]
    return 1e3 * sum(per) / len(per)
