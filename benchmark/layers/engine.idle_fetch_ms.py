"""Milliseconds a launch the device sits idle under the host's blocking
reads of what the launch left (``serve.decode.fetch``: the token buffer, the
per-round counts, a noted round's notes, a grouped model's counts): the
``fetch`` pieces of the stretch's idle gaps (``pb_cycle``) over its
launches. None where the program has no such span."""

import pb_cycle


def read(facts):
    return pb_cycle.idle_ms_per_launch(facts, "fetch")
