"""Device milliseconds a step under the scope ``optimizer``: the clip by
global norm, the Adam update and its application to the parameters."""

import pb_spans


def read(facts):
    return pb_spans.scope_ms_per_step(facts, "optimizer")
