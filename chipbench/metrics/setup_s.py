"""Process start to the window's opening: JAX start-up, weights, server
build, compilation or cache loads, warm-up, and the ramp of the queue."""


def read(run):
    return run.setup_s
