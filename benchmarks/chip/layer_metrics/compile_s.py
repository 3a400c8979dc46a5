"""Seconds JAX spent tracing, lowering and compiling during set-up, from
its own monitoring events (a warm persistent cache shortens the last)."""


def read(rec):
    return rec["compile_s"]
