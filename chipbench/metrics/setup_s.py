"""Process start to the start of the window: JAX start-up, generate and
load, warm-up (with compiles on a checkout's first run)."""


def read(run):
    return run.setup_s
