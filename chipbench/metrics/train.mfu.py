"""Model FLOP/s utilisation: tokens/s x FLOPs a trained token requires
(the family's ``train_flops_per_token``, recomputation not counted)
over chips x peak bf16 FLOP/s."""


def read(ctx):
    if ctx.get("peak") is None:
        return None
    rate = ctx["end_to_end"]["train_tokens_per_s"]
    per_token = ctx["family"].train_flops_per_token(
        ctx["sizes"], int(ctx["mix"]["seq_len"]))
    return 100.0 * rate * per_token / (ctx["chips"] * ctx["peak"]["flops"])
