"""The prefill programs' share of the chip's bf16 peak: 2 x matrix
parameters x the real (unpadded) prompt tokens they took in / their device
time in the trace (the ``XLA Modules`` events named ``prefill``) / the peak.
Bucket padding counts against it."""


def read(facts):
    probe = facts.get("probe")
    if not probe or not probe["prefills"]:
        return None
    runs, seconds = facts["trace"].module_time(r"prefill")
    if not runs:
        raise ValueError("the traced window ran prefills and the trace "
                         "holds no program named 'prefill'")
    flops = facts["cell"].family.forward_flops_per_token(facts["cfg"])
    return (100.0 * flops * probe["prompt_tokens"] / seconds
            / facts["peaks"]["bf16_flops_per_s"])
