"""Kernels, training: model FLOP/s utilization. Per token, 6 x the
parameters that multiply (the embedding gather excluded) plus attention's
forward and backward operations from shapes (causal), times tokens per
second, over the table's bf16 peak. Recomputed operations (remat) are not
counted."""


def read(obs):
    train, peaks = obs.get("train"), obs.get("peaks")
    if not train or not peaks:
        return None
    fam, cfg = obs["family"], obs["config"]
    per_token = 6 * fam.matmul_params(cfg) + 3 * fam.attention_flops_per_token(
        cfg, obs["traffic"]["seq"])
    rate = train["steps"] * train["tokens_per_step"] / train["elapsed_s"]
    chips = train["device"]["count"]
    return 100.0 * per_token * rate / (peaks["bf16_flops_per_s"] * chips)
