"""The share of the rows the prefill programs ran that were padding: 1 -
real prompt tokens over bucket lengths, over the ``serve.prefill`` spans of
the traced stretch."""

import pb_spans


def read(facts):
    cap = pb_spans.read(facts)
    spans = cap.spans.get("serve.prefill") if cap is not None else None
    if not spans:
        return None
    padded = sum(sp.stats["bucket"] for sp in spans)
    if not padded:
        return None
    return 100.0 * (1.0 - sum(sp.stats["prompt_len"] for sp in spans)
                    / padded)
