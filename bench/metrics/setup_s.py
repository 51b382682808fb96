"""setup_s: process start to the first timed solve, in seconds (TPU
start-up, the system built on the device from the seed, any factorization,
compilation or reading the compile cache, one warm-up)."""


def read(run):
    return run.setup_s
