"""The least time the chip could take for the traced stretch's decode steps
of a model with routed experts and two kinds of cache, over the device time of
the decode programs (``resident`` | ``decode``). What a step must read: the
non-expert weights and the head once; the three matrices of every held expert
that a layer's routing gave a row (``experts_touched``); every live slot's
cached rows up to its position in the full layers and up to the window's
length in the window layers (``full_rows_read``, ``window_rows_read``: rows x
layers). Steps and counts are the program's own, from its ``serve.decode.done``
spans. Bytes / the chip's HBM bandwidth bounds the step (16 slots are far from
the compute roof). ``decode.step_roofline``'s bytes, from ``Probe``, count
every expert and every row of a window layer, and are not used here. None
where the program counts no such thing."""

from pb_core import load_by_path

_rows = load_by_path("layers/moe.rows_per_expert_read.py")


def read(facts):
    got = _rows.launch_counts(facts, "steps", "experts_touched",
                              "full_rows_read", "window_rows_read")
    if not got or not got[0]:
        return None
    runs, seconds = facts["trace"].module_time(r"resident|decode")
    if not runs:
        return None
    steps, touched, full_rows, window_rows = got
    fam, cfg = facts["cell"].family, facts["cfg"]
    must = (steps * fam.decode_weight_bytes(cfg)
            + touched * fam.expert_bytes(cfg)
            + (full_rows + window_rows) * fam.cache_row_bytes(cfg))
    return 100.0 * must / facts["peaks"]["hbm_bytes_per_s"] / seconds
