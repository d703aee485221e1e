"""device_idle_share.train: the share of the traced train window in
which no operation ran on a chip, averaged over the chips.
Moves ``train_tokens_per_s``."""


def read(ctx):
    found = ctx.get("trace")
    return None if found is None else 100.0 * found["idle_share"]
