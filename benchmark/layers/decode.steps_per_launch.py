"""Decode steps a launch: the mean ``steps`` of the engine's
``serve.decode.done`` spans in the traced stretch. A launch ends when a
slot finishes or the resident horizon is reached; each end costs the host's
sync, retirement, a prefill and a relaunch."""

import pb_spans


def read(facts):
    done = pb_spans.decode_done(facts)
    if not done:
        return None
    return sum(sp.stats["steps"] for sp in done) / len(done)
