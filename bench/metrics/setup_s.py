"""Set-up seconds: weights, registration with every executable warmed,
the sealed pool, one batch of each bucket end to end, the pool filled,
and the traffic's warm-up load up to the window's open."""


def read(run):
    return run.setup_s
