"""study_mfu: the members' model FLOPs of one study over the study's
makespan and the chips' bf16 peak: the whole study's share of the
peak, which bounds any gain claimed on ``study_makespan_s``."""


def read(ctx):
    if not ctx.get("peak_flops") or "study_flops" not in ctx:
        return None
    return 100.0 * ctx["study_flops"] / (
        ctx["makespan_s"] * ctx["chips"] * ctx["peak_flops"])
