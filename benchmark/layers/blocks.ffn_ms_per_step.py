"""Device milliseconds a step under the scope ``ffn`` (the blocks'
feed-forward, its dropouts, the residual add and the closing norm)."""

import pb_spans


def read(facts):
    return pb_spans.scope_ms_per_step(facts, "ffn")
