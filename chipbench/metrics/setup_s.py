"""Process start to the first timed job: JAX start-up, trace generation,
compiles or compile-cache loads, and the warm-up job (host clock)."""


def read(run):
    return run.setup_s
