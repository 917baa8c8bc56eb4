"""Device milliseconds a step under the scopes ``head`` or ``loss``: the
projection to the vocabulary, the cross-entropy, and their backward."""

import pb_spans


def read(facts):
    return pb_spans.scope_ms_per_step(facts, "head", "loss")
