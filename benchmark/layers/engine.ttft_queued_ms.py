"""The first stage of a request's time to its first token, in milliseconds:
from ``submit`` to the start of its admission, the mean ``queued_ms`` of the
traced stretch's ``serve.first_token`` spans. None where the program opens
no such span."""

import pb_cycle


def read(facts):
    return pb_cycle.first_token_ms(facts, "queued_ms")
