"""Model FLOP/s utilization of the whole training step: required FLOPs per
token (the family's arithmetic; recomputation not counted) x tokens per
second of the traced window / (chips x the chip's bf16 peak)."""


def read(facts):
    flops = facts["cell"].family.train_flops_per_token(facts["cfg"])
    rate = facts["tokens"] / facts["window_s"]
    peak = facts["peaks"]["bf16_flops_per_s"] * facts["chips"]
    return 100.0 * flops * rate / peak
