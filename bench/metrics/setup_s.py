"""setup_s: process start to the first timed call: imports, the data made
on the device from the seed, compiles or loads from the compile cache, and
the warm-up requests."""


def read(run):
    return run.setup_s
