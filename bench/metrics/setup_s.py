"""setup_s: seconds from the process's start to the window's start:
imports, JAX start-up, data and weights made from the seed, lowering,
compilation (or reading it from the persistent cache) and warm-up."""


def read(r):
    return r.setup_s
