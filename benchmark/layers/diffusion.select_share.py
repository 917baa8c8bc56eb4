"""The share of the decode programs' device time that picking the positions
to reveal costs: the operations under the scope ``head``, which in a block
round holds a denoise pass's projection of the block's rows to the vocabulary
and, under ``diffusion_select`` inside it, the softmax, the confidence and the
reveal. Device time of the programs named ``resident`` or ``decode``, as
``decode.cache_share``. None where no operation carries ``diffusion_select``
(a program without a block round)."""

import pb_spans


def read(facts):
    cap = pb_spans.read(facts)
    if cap is None or not any("diffusion_select" in (op.op_name or "")
                              for op in cap.ops):
        return None
    return pb_spans.decode_share(facts, lambda op: op.scope == "head")
