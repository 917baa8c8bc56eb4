"""The least time the chip could take for one step's required FLOPs and
bytes (the larger of FLOPs / peak FLOP/s and bytes / peak bytes/s; for this
family FLOPs bound it) over the device's busy time per step in the trace."""


def read(facts):
    fam, cfg, peaks = facts["cell"].family, facts["cfg"], facts["peaks"]
    tokens_per_step = facts["tokens"] / facts["steps"]
    flops = fam.train_flops_per_token(cfg) * tokens_per_step / facts["chips"]
    byts = fam.train_bytes_per_step(cfg, facts["rows"]) / facts["chips"]
    least = max(flops / peaks["bf16_flops_per_s"],
                byts / peaks["hbm_bytes_per_s"])
    busy_per_step = facts["trace"].busy_s / facts["steps"]
    return 100.0 * least / busy_per_step
