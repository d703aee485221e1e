"""train_attn_kernel_share: the share (%) of the train step's attention
sub-layers that the program traced through the Pallas flash kernel: its
tally ``papas.attn.kernel`` over that and ``papas.attn.xla``
(``models/attention.py:attn_block``, ``core/telemetry.py:tally``), read
in-process. None where no attention sub-layer was traced (an
attention-free model) or the program keeps no tallies. Moves
``train_tokens_per_s``."""


def read(ctx):
    try:
        from repro.core.telemetry import tallies
    except ImportError:
        return None
    counts = tallies()
    kernel = counts.get("papas.attn.kernel", 0)
    traced = kernel + counts.get("papas.attn.xla", 0)
    return 100.0 * kernel / traced if traced else None
