"""``moe.experts_roofline`` in a cell whose decode program runs the block
round: the same reader (``layers/moe.experts_roofline.py``, loaded by path),
under a name of its own because that metric's list is held to the cell it
came with. A launch's ``experts_touched`` and ``expert_rows`` count every
pass, denoise and commit alike: each reads an expert's three matrices once.
None where the program has no such scope or counts."""

from pb_core import load_by_path

read = load_by_path("layers/moe.experts_roofline.py").read
