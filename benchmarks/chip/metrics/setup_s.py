"""Process start to the window's start: weights made from the seed,
programs warmed from the compile cache, a closed loop primed."""


def read(run):
    return run.setup_s
