"""The share of the decode programs' device time spent under the scope
``kv_cache``: the cache's update and its two reads (every cached row of k
for the scores, of v for the mix) in each layer's decode. Device time of the
programs named ``resident`` or ``decode``, as ``decode.step_roofline``."""

import pb_spans


def read(facts):
    return pb_spans.decode_share(facts, lambda op: op.scope == "kv_cache")
