"""apex_tpu.normalization — fused normalization layers (SURVEY.md §2.5)."""

from .fused_layer_norm import (FusedLayerNorm, fused_layer_norm,  # noqa: F401
                               fused_layer_norm_affine)
from .rms_norm import (RMSNorm, rms_norm, gated_rms_norm,  # noqa: F401
                       gated_rms_norm_factors)
from .fused_bn_act import (bn_relu_residual,  # noqa: F401
                           bn_act_epilogue_ref)
