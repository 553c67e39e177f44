"""TPU kernels (pallas) for the hot ops.

The compute path is JAX/XLA; these kernels take over where hand-tiling
beats the compiler — flash attention (the reference's equivalent hot
path is the cuDNN/cuBLAS attention chain in its benchmark models), and
the `kernels/` registry (paged decode-attention, fused sparse
optimizers) that the lowering rules dispatch into behind the
per-kernel `PADDLE_TPU_KERNELS` knob (docs/perf.md#kernel-layer).
"""
from .flash_attention import flash_attention, flash_attention_lse, \
    flash_attention_sharded, flash_attention_summary, merge_lse, \
    reference_attention, reference_attention_summary
from . import kernels

__all__ = ['flash_attention', 'flash_attention_lse',
           'flash_attention_sharded', 'flash_attention_summary', 'merge_lse',
           'reference_attention', 'reference_attention_summary', 'kernels']
