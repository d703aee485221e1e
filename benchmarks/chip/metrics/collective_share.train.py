"""collective_share.train: the share (%) of the traced train window in
which a chip ran a collective (all-reduce, all-gather, reduce-scatter,
all-to-all, collective-permute; ``trace.py``), averaged over the chips.
None where the trace holds no device operation. Moves
``train_tokens_per_s``."""


def read(ctx):
    found = ctx.get("trace")
    if found is None or not found.get("window_s"):
        return None
    return 100.0 * found["collective_s"] / found["window_s"]
