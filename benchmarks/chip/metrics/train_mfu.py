"""train_mfu: the whole train step's share of the chips' bf16 peak.

    model FLOPs per token (flops.py) * train_tokens_per_s
    / (chips * peak bf16 FLOP/s)

Recomputation is not counted. Moves ``train_tokens_per_s``.
"""


def read(ctx):
    if not ctx.get("peak_flops") or "flops_per_token" not in ctx:
        return None
    return 100.0 * ctx["flops_per_token"] * ctx["tokens_per_s"] / (
        ctx["chips"] * ctx["peak_flops"])
