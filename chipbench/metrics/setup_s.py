"""Process start to the first timed request or onboard: imports, data,
fleet load (or fit), compile or cache reads, warm-up."""


def read(obs):
    return obs.setup_s
