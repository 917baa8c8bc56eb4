"""The share of the decode programs' device time spent in the expert layer:
the operations under the program's scopes ``moe_router``, ``moe_experts`` and
``moe_shared`` (opened inside ``ffn``), and the compiler's own grouped-product
kernel, whose ``op_name`` keeps no scope path (``ragged-dot-*``). Device time
of the programs named ``resident`` or ``decode``, as ``decode.cache_share``.
Says that the mechanism does most of the work of a step. None where the
program has no such scope."""

import re

import pb_spans

MOE = re.compile(r"(?:^|/)(?:\w+\()*(moe_router|moe_experts|moe_shared)\)*"
                 r"(?=[/:]|$)")


def moe_scope(op):
    """The innermost expert-layer scope on the operation's path, or None;
    the grouped-product kernel and its metadata count as ``moe_experts``."""
    found = MOE.findall(op.op_name or "")
    if found:
        return found[-1]
    if (op.op_name or op.name or "").startswith("ragged-dot"):
        return "moe_experts"
    return None


def read(facts):
    cap = pb_spans.read(facts)
    if cap is None or not any(moe_scope(op) for op in cap.ops):
        return None
    return pb_spans.decode_share(facts, lambda op: moe_scope(op) is not None)
