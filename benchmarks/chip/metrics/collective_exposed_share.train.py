"""collective_exposed_share.train: the share (%) of the traced train
window in which a chip ran a collective that no other ``XLA Ops``
operation of that chip overlapped (``trace.py``'s
``collective_exposed_s``), averaged over the chips. ``trace.py`` counts
a ``while`` as such an operation, and it spans its body, so the
collectives inside the layer scan read as overlapped. None where the
trace holds no device operation. Moves ``train_tokens_per_s``."""


def read(ctx):
    found = ctx.get("trace")
    if found is None or not found.get("window_s"):
        return None
    return 100.0 * found["collective_exposed_s"] / found["window_s"]
