"""The least time the chip could take for the traced stretch's passes of the
block round, over the device time of the decode programs (``resident`` |
``decode``). What a pass must read: the layers' non-expert weights once; the
head once more if it is a denoise pass (the commit pass has none); the three
matrices of every expert that a layer's routing gave a row
(``experts_touched``); every live slot's cached rows up to the end of its block
in every layer (``full_rows_read``: rows x layers). Passes and counts are the
program's own, from its ``serve.decode.done`` spans: ``steps`` counts the
passes every slot went through together, ``T`` of every ``T + 1`` of them
denoise passes. Bytes / the chip's HBM bandwidth bounds a pass (128 rows are
far from the compute roof). The byte functions are the family's. None where
the program counts no such thing."""

from pb_core import load_by_path

_rows = load_by_path("layers/moe.rows_per_expert_read.py")


def read(facts):
    got = _rows.launch_counts(facts, "steps", "blocks", "experts_touched",
                              "full_rows_read")
    if not got or not got[0]:
        return None
    runs, seconds = facts["trace"].module_time(r"resident|decode")
    if not runs:
        return None
    steps, _, touched, rows = got
    fam, cfg = facts["cell"].family, facts["cfg"]
    T = cfg["generation"]["denoise_steps"]
    must = (steps * fam.decode_weight_bytes(cfg)
            + steps * T / (T + 1) * fam.head_bytes(cfg)
            + touched * fam.expert_bytes(cfg)
            + rows * fam.cache_row_bytes(cfg))
    return 100.0 * must / facts["peaks"]["hbm_bytes_per_s"] / seconds
