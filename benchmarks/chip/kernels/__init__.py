"""Operation and byte counts of Pallas kernels, one module per kernel.

No kernel runs on the model path yet, so no module is here. The change
that puts a kernel on the path adds ``<kernel>.py`` with the kernel's
operations and bytes as functions of its shapes, and a reader
``metrics/<kernel>_roofline.py`` that divides the larger of
operations / peak FLOP/s and bytes / peak bytes/s by the kernel's
device time from the trace.
"""
