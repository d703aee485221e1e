"""device_idle_share.study: the share of the traced study window in
which no operation ran on a chip, averaged over the chips.
Moves ``study_makespan_s``."""


def read(ctx):
    found = ctx.get("trace")
    return None if found is None else 100.0 * found["idle_share"]
