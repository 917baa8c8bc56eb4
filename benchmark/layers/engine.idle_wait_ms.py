"""Milliseconds a launch the device sits idle while the host still waits
for the launch's round count (``serve.decode.wait``): at its end the device
done and the host not yet told, at its start the launch queued and the
device not yet running. Each idle gap of the first chip in the traced stretch is
cut at the launch cycle's phase bounds (``pb_cycle``) and this is the
``wait`` pieces' sum over the launches (``serve.decode.done`` spans). None
where the program has no such span."""

import pb_cycle


def read(facts):
    return pb_cycle.idle_ms_per_launch(facts, "wait")
