"""Pallas TPU kernels for perf-critical hot spots (+ jnp oracles).

flash_attention — blockwise GQA attention (causal / SWA / bidirectional),
                  with its own backward kernels
ssd_scan        — Mamba2 SSD chunked scan
grouped_matmul  — megablox-style ragged expert GEMM
"""
from . import ops, ref
from .flash_attention import flash_attention as flash_attention_kernel
from .moe_gmm import grouped_matmul as grouped_matmul_kernel
from .ssd_scan import ssd_scan as ssd_scan_kernel

__all__ = ["ops", "ref", "flash_attention_kernel", "grouped_matmul_kernel",
           "ssd_scan_kernel"]
