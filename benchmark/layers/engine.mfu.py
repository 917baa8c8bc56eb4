"""The serve step's share of the chip's bf16 peak: 2 x matrix parameters x
(prompt + output tokens of the requests the window finished) per second of
the window / the peak."""


def read(facts):
    flops = facts["cell"].family.forward_flops_per_token(facts["cfg"])
    tokens = facts["prompt_tokens"] + facts["out_tokens"]
    peak = facts["peaks"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * flops * tokens / facts["window_s"] / peak
