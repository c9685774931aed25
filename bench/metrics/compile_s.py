"""Seconds JAX spent compiling (or fetching from its cache) in set-up."""


def read(run):
    return run.setup_compile_s
