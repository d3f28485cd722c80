"""Seconds from process start to the first due request: weights made
on the device from the seed, the engine and its pool, and the warm-up
of this cell's programs (from the compile cache after the first run)."""


def read(run):
    return run.setup_s
