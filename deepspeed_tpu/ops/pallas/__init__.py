"""Public surface of the Pallas TPU kernels.

Call sites import the kernel entry points from here
(``from deepspeed_tpu.ops.pallas import flash_decode_paged``) instead of
deep-importing the defining modules — the module layout below this
package is an implementation detail (the flash-attention forward and
both backward kernels live in one file today; the static analyzer
`analysis/kernels.py` doesn't care either way, it finds every
``pallas_call`` in the traced program).

Every kernel auto-selects Pallas interpret mode off-TPU, so this
package imports (and the kernels run, slowly) on CPU test meshes.
"""

from deepspeed_tpu.ops.pallas.chunk_prefill import flash_prefill_paged
from deepspeed_tpu.ops.pallas.flash_attention import (
    DEFAULT_MASK_VALUE,
    dense_attention,
    flash_attention,
)
from deepspeed_tpu.ops.pallas.flash_decode import (
    DEFAULT_BLOCK_K,
    KernelGeometryError,
    flash_decode_paged,
)
from deepspeed_tpu.ops.pallas.fused_adam import pallas_adam_update
from deepspeed_tpu.ops.pallas.latent_prefill import flash_prefill_latent_block
from deepspeed_tpu.ops.pallas.window_prefill import window_prefill_band

__all__ = [
    "DEFAULT_BLOCK_K",
    "DEFAULT_MASK_VALUE",
    "KernelGeometryError",
    "dense_attention",
    "flash_attention",
    "flash_decode_paged",
    "flash_prefill_latent_block",
    "flash_prefill_paged",
    "pallas_adam_update",
    "window_prefill_band",
]
