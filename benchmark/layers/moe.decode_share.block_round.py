"""``moe.decode_share`` in a cell whose decode program runs the block round:
the same reader (``layers/moe.decode_share.py``, loaded by path), under a
name of its own because that metric's list is held to the cell it came with.
There the decode programs' device time is the denoise and commit passes', and
the grouped product is the tiled kernel under ``moe_experts``. None where the
program has no such scope."""

from pb_core import load_by_path

read = load_by_path("layers/moe.decode_share.py").read
