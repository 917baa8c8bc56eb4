"""Seconds JAX spent in the backend compiler (cache retrieval included)
during set-up, from its own compile events."""


def read(facts):
    return facts["setup_compile_s"]
