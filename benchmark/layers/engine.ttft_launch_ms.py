"""The last stage of a request's time to its first token, in milliseconds:
from the dispatch of the launch it rides to the token (under a block round
the first block) on the host, the mean ``launch_ms`` of the traced stretch's
``serve.first_token`` spans. None where the program opens no such span."""

import pb_cycle


def read(facts):
    return pb_cycle.first_token_ms(facts, "launch_ms")
