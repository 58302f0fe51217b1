"""Pallas TPU kernels — the hot-op layer.

Role parity: the reference's hand-fused CUDA ops (paddle/fluid/operators/
fused/ — fused_attention_op.cu, fused_multi_transformer_op.cu) and its
jit'ed CPU math (operators/math/jit).  On TPU, XLA already fuses elementwise
chains into matmuls, so only genuinely structured kernels live here:
flash attention (+ring variant for sequence parallelism), the ragged
paged-attention kernel behind the serving engine's KV cache (equal heads
over every page, or grouped heads over selected pages), the lightning
(decayed linear) attention kernel over a per-row recurrent state, the
selective state-space scan (input-dependent decay) over the same, and the
two ragged grouped matrix products of a dropless sparse-expert layer, which
fetch their token rows by id and add their weighted results by id.
"""
from .expert_matmul import expert_matmul, expert_matmul_add  # noqa: F401
from .flash_attention import flash_attention, flash_attention_available  # noqa: F401
from .lightning_attention import lightning_attention  # noqa: F401
from .paged_attention import ragged_paged_attention  # noqa: F401
from .ssd_scan import ssd_scan  # noqa: F401
