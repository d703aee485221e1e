"""Small configurations for the CPU tests: the cells' files with the
sizes cut to what a test run holds."""
import os
import time
from pathlib import Path

from benchmarks.chip import harness

ROOT = Path(__file__).resolve().parents[3]

DANUBE = {"n_layers": 2, "d_model": 64, "n_heads": 4, "n_kv_heads": 2,
          "head_dim": 16, "d_ff": 128, "vocab_size": 256,
          "layer_types": ["swa", "swa"], "window": 16, "mlp_act": "silu",
          "tie_embeddings": False, "rope_theta": 10000.0, "norm_eps": 1e-6,
          "param_dtype": "float32", "compute_dtype": "bfloat16"}
MAMBA2 = {"n_layers": 2, "d_model": 64, "n_heads": 0, "n_kv_heads": 0,
          "head_dim": 0, "d_ff": 0, "vocab_size": 256,
          "layer_types": ["ssm", "ssm"], "ssm_state": 16, "ssm_expand": 2,
          "ssm_head_dim": 16, "ssm_groups": 1, "ssm_conv": 4,
          "ssm_chunk": 16, "tie_embeddings": True, "norm_eps": 1e-6,
          "param_dtype": "float32", "compute_dtype": "bfloat16"}


def cpu_env() -> dict:
    return {**os.environ, "JAX_PLATFORMS": "cpu"}


def train_spec(model: dict, seed: int = 2**33 + 7, seconds: float = 0.5,
               batch: int = 2, seq: int = 64) -> dict:
    import jax
    arch = "mamba2-780m" if "ssm" in model["layer_types"] else \
        "h2o-danube-1.8b"
    return {"config": {"arch": arch, "model": model,
                       "mesh": {"data": 1, "model": 1}},
            "traffic": {**harness.load("traffic", "train-4x2048"),
                        "batch": batch, "seq": seq},
            "seed": seed, "seconds": seconds, "trace": False,
            "devices": jax.devices()[:1], "t0": time.perf_counter()}
