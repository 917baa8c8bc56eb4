"""The share of the decode programs' device time spent in ``copy``
instructions under no scope: copies the compiler put in, which no line of
the program asks for (the whole-slab copies of the resident loop's carry)."""

import pb_spans


def read(facts):
    return pb_spans.decode_share(
        facts, lambda op: op.scope is None and pb_spans.is_copy(op))
