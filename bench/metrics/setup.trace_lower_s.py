"""Seconds JAX spent tracing and lowering programs to StableHLO during
set-up (``jax.monitoring``): the part of a warm start that no persistent
cache saves."""


def read(rec):
    return rec["setup"]["trace_lower_s"]
