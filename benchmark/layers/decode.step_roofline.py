"""The least time the chip could take for the traced window's decode steps
over the device time of the decode programs. A step must read the weights
once (at their served type) and every live slot's cached rows up to its
position; bytes / the chip's HBM bandwidth bounds it (a 16-slot step is far
from the compute roof). Rows past a slot's position, and dead slots, are not
counted. Device time: the ``XLA Modules`` events named ``resident`` or
``decode``."""


def read(facts):
    probe = facts.get("probe")
    if not probe or not probe["decode_steps"]:
        return None
    runs, seconds = facts["trace"].module_time(r"resident|decode")
    if not runs:
        raise ValueError("the traced window ran decode launches and the "
                         "trace holds no program named 'resident'/'decode'")
    least = probe["decode_bytes"] / facts["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least / seconds
