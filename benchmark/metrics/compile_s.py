"""Seconds the XLA compile path took in set-up (compile_cache.stats())."""


def read(run):
    return run.setup_compile_s
