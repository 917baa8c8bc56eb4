"""Whether the profiled stretch is like the window it follows: the stretch's
mean of ``fetch + turn + caller`` a launch (what the host adds between a
launch's round count and the next dispatch) from the launch cycle's spans,
over the whole process's from the engine's ``serve.engine.cycle.*_sec``
timers, stalled phases left out (``pb_cycle``). 1 where the profiler
changes nothing; well over 1 where the host was slower under it, and the
stretch's idle share and what divides by it are not the window's. None
where the program keeps neither."""

import pb_cycle


def read(facts):
    return pb_cycle.traced_gap_ratio(facts)
