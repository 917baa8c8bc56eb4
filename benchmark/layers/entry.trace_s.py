"""Seconds JAX spent tracing and lowering during set-up: the part of a warm
set-up that no compile cache removes."""


def read(facts):
    return facts["setup_trace_s"]
