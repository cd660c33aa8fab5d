"""Process start to the start of the measured window: JAX and chip
start-up, making the instances, loading or compiling every program and
one full warm-up solve (host clock)."""


def read(run):
    return run.setup_s
