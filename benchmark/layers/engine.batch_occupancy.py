"""The share of the engine's slots that were live in a decode step:
``live`` over the slots, over the ``serve.decode.done`` spans of the traced
stretch, each weighted by its ``steps``."""

import pb_spans


def read(facts):
    done = pb_spans.decode_done(facts)
    if not done:
        return None
    slots = facts["traffic"]["engine"]["slots"]
    steps = sum(sp.stats["steps"] for sp in done)
    if not steps:
        return None
    return 100.0 * sum(sp.stats["live"] * sp.stats["steps"]
                       for sp in done) / (slots * steps)
