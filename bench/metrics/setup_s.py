"""setup_s (s): from the start of the process to the opening of the window:
imports, the deployment made from the seed, the service built and its fleet
registered, and every shape warmed up (compiled, or read from the cache)."""


def read(run):
    return run.setup_s
