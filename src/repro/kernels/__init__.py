"""Pallas-TPU kernels for the serving hot spots (validated interpret=True on
CPU against the pure-jnp oracles in ref.py):

  flash_attention  — blocked online-softmax prefill attention (causal/window)
  decode_attention — single-token GQA attention over a long KV cache
  ssd_scan         — Mamba-2 chunked SSD scan with VMEM state carry
  gus_pallas       — fused GUS greedy-assignment kernel (utility + feasibility
                     + capacity-aware argmax loop), bit-parity-tested against
                     the NumPy and XLA schedulers in repro.core.gus
  hier_pallas      — fused hierarchical class allocator, parity-tested
                     against repro.core.aggregation.hier_cells_np

On a TPU the scheduler kernels compile (Mosaic); on the CPU they run in
interpret mode (gus_pallas.pallas_interpret decides from the platform).
"""
from . import ops, ref
from .flash_attention import flash_attention as flash_attention_kernel
from .decode_attention import decode_attention as decode_attention_kernel
from .gus_pallas import gus_assign_pallas
from .ssd_scan import ssd_scan as ssd_scan_kernel

__all__ = [
    "ops",
    "ref",
    "flash_attention_kernel",
    "decode_attention_kernel",
    "gus_assign_pallas",
    "ssd_scan_kernel",
]
