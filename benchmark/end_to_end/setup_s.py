"""Process start to the window's start: JAX and the chip, the data, the
placement probe, and the warm-up calls with their compiles or cache
loads."""


def read(run):
    return run.setup_s
