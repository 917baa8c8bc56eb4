"""The grouped product's share of its roofline over the traced stretch,
whatever implements it. The least time: for the decode launches and for the
prefill programs apart, the larger of (bytes of the touched experts' weights /
HBM bandwidth) and (2 x 3 x hidden x width x token-expert pairs / bf16 peak),
the two added. Touched experts and pairs are the program's own counts
(``experts_touched``, ``expert_rows`` and their ``prefill_`` twins on the
``serve.decode.done`` spans: an expert with a row in a layer of a step has
its three matrices read once). Over the device time under the scope
``moe_experts``, the compiler's ``ragged-dot`` kernel counted in. The byte and
FLOP functions are the family's. None where the program has no such scope or
counts."""

from pb_core import load_by_path

import pb_spans

_share = load_by_path("layers/moe.decode_share.py")
_rows = load_by_path("layers/moe.rows_per_expert_read.py")


def read(facts):
    cap = pb_spans.read(facts)
    got = _rows.launch_counts(facts, "experts_touched", "expert_rows",
                              "prefill_experts_touched",
                              "prefill_expert_rows")
    if cap is None or not got:
        return None
    ns = sum(op.self_ns for op in cap.ops
             if _share.moe_scope(op) == "moe_experts")
    if not ns:
        return None
    fam, cfg, peaks = facts["cell"].family, facts["cfg"], facts["peaks"]
    least = 0.0
    for touched, rows in (got[:2], got[2:]):
        least += max(
            touched * fam.expert_bytes(cfg) / peaks["hbm_bytes_per_s"],
            rows * fam.expert_flops_per_row(cfg) / peaks["bf16_flops_per_s"])
    return 100.0 * least / (ns / 1e9)
