"""Device milliseconds a step in operations that carry the remat marker
(``rematted_computation``: a forward that runs again for its backward),
whatever their scope: it cuts across the scopes' metrics and is part of
them, not a further summand."""

import pb_spans


def read(facts):
    return pb_spans.scope_ms_per_step(facts, remat=True)
