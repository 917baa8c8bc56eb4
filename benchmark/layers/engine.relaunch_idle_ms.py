"""Milliseconds the device sits idle for each decode launch: its idle time
inside the ``serve.tick`` spans of the traced stretch (each gap put down to
the innermost program span over its middle, and counted where a
``serve.tick`` covers that middle) over the launches (``serve.decode.done``
spans). What a launch's end costs the device: the host's sync, retirement,
admission, prefill dispatch and relaunch."""

import pb_spans


def read(facts):
    done = pb_spans.decode_done(facts)
    if not done:
        return None
    gaps = pb_spans.read(facts).gaps_by_span(within="serve.tick")
    return sum(gaps.values()) / 1e6 / len(done)
