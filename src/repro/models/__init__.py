"""Model substrate: configs, layers, and the 10 assigned architectures."""
from .config import ArchConfig
from .model import Model, synthetic_batch
from .transformer import decode_step, forward, init_cache, init_params, loss_fn

__all__ = [
    "ArchConfig", "Model", "synthetic_batch",
    "decode_step", "forward", "init_cache", "init_params", "loss_fn",
]
