"""The gang's share of the chips' bf16 peak over the traced window: model
operations per token (forward and backward, no recomputation,
``flops.train_flops_per_token``) times the tokens of the window's steps,
over chips x busy time x peak."""


def read(rec, red):
    if red["busy_s"] <= 0 or not rec["tokens"]:
        return None
    ops = rec["flops_per_token"] * rec["tokens"]
    return 100.0 * ops / (rec["n_chips"] * red["busy_s"]
                          * rec["peaks"]["flops_bf16"])
