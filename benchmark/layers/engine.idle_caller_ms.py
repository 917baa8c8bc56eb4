"""Milliseconds a launch the device sits idle while the engine is not
running: under no ``serve.tick`` span (the caller's work between two ticks:
in the closed loop its bookkeeping and the next requests' ``submit``): the
``caller`` pieces of the stretch's idle gaps (``pb_cycle``) over its
launches. None where the program has no ``serve.decode.wait`` span."""

import pb_cycle


def read(facts):
    return pb_cycle.idle_ms_per_launch(facts, "caller")
