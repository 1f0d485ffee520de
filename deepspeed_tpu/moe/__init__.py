from deepspeed_tpu.moe.dropless import dropless_moe
from deepspeed_tpu.moe.layer import (MoE, MoEConfig, compute_capacity,
                                     moe_param_spec, top_k_gating)

__all__ = ["MoE", "MoEConfig", "compute_capacity", "dropless_moe",
           "moe_param_spec", "top_k_gating"]
