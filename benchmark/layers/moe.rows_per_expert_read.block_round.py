"""``moe.rows_per_expert_read`` in a cell whose decode program runs the block
round: the same reader (``layers/moe.rows_per_expert_read.py``, loaded by
path), under a name of its own because that metric's list is held to the cell
it came with. A pass routes slots x block length x top-k pairs, so the number
is near ``32 x 4 x 8 / 128``: 8 rows for every expert read. None where the
program counts no such thing."""

from pb_core import load_by_path

read = load_by_path("layers/moe.rows_per_expert_read.py").read
