"""Device milliseconds a step under the scope ``attention`` (the blocks'
attention, its dropout, the residual add and the norm that closes the
branch; forward, re-forward and backward alike), over the step programs run
in the traced window."""

import pb_spans


def read(facts):
    return pb_spans.scope_ms_per_step(facts, "attention")
