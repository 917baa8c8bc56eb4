"""Milliseconds a launch the device sits idle under the host's own turn:
inside a ``serve.tick`` and under neither ``serve.decode.wait`` nor
``serve.decode.fetch`` (retirement, the tick's end, the next tick's reaping,
admissions with their prefill dispatches, the launch's dispatch): the
``turn`` pieces of the stretch's idle gaps (``pb_cycle``) over its launches.
None where the program has no ``serve.decode.wait`` span."""

import pb_cycle


def read(facts):
    return pb_cycle.idle_ms_per_launch(facts, "turn")
