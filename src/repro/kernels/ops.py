"""Public jit'd wrappers for the Pallas kernels.

Every kernel compiles for the TPU by default (``interpret=False``).  On
the CPU pass ``interpret=True``: interpret mode executes the kernel
bodies in Python for correctness validation (the tests and
``benchmarks/kernels_bench.py`` do) — same BlockSpecs, same code."""
from repro.kernels.decode_attention import decode_attention
from repro.kernels.fedavg_reduce import fedavg_reduce
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gru_cell import gru_seq
from repro.kernels.mamba_scan import mamba_chunk_scan
from repro.kernels.paged_decode_attention import (
    paged_decode_attention, paged_mla_decode_attention)
from repro.kernels.topk_router import topk_router

__all__ = ["decode_attention", "fedavg_reduce", "flash_attention",
           "gru_seq", "mamba_chunk_scan", "paged_decode_attention",
           "paged_mla_decode_attention", "topk_router"]
