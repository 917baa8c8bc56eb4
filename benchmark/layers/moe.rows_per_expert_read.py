"""Token-expert pairs a decode step computes for every held expert whose
weights it reads: ``expert_rows / experts_touched`` summed over the traced
stretch's decode launches, from the attributes of the program's
``serve.decode.done`` spans (its counters ``serve.moe.expert_rows`` and
``serve.moe.experts_touched``, read in the fetch a launch makes anyway). At
1 every expert's 18.9 MB are read for one row; the batch decides it. None
where the program counts no such thing."""

import pb_spans


def launch_counts(facts, *names):
    """The sums of ``names`` over the window's ``serve.decode.done`` spans,
    or None where there are none or a span lacks one of them."""
    done = pb_spans.decode_done(facts)
    if not done or any(n not in sp.stats for sp in done for n in names):
        return None
    return [sum(int(sp.stats[n]) for sp in done) for n in names]


def read(facts):
    got = launch_counts(facts, "expert_rows", "experts_touched")
    if not got or not got[1]:
        return None
    return got[0] / got[1]
