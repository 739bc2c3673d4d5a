"""Hot-path ops: pallas TPU kernels with portable jnp fallbacks.

Kernel policy (pallas_guide.md): write pallas only where XLA's own fusion
leaves bandwidth on the table — blockwise attention is the one op where the
O(T^2) intermediate must never exist, and decode attention over the serving
page pool (paged_attention.py) the one where a gather of every slot's whole
logical cache must not. Elementwise chains (rmsnorm, rope, swiglu, losses)
are written in plain jnp and left to XLA to fuse into the neighbouring
matmuls.
"""

from oim_tpu.ops.attention import attention, flash_attention, mha_reference
from oim_tpu.ops.norms import layernorm, rmsnorm
from oim_tpu.ops.rope import apply_rope, rope_frequencies
from oim_tpu.ops.losses import softmax_cross_entropy

__all__ = [
    "attention",
    "flash_attention",
    "mha_reference",
    "rmsnorm",
    "layernorm",
    "apply_rope",
    "rope_frequencies",
    "softmax_cross_entropy",
]
