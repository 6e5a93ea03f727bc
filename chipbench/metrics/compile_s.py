"""Seconds JAX spent tracing, lowering and compiling (or reading the
persistent cache) during set-up."""


def read(rec):
    return rec["run"]["compile_s"]
