"""apex_tpu.ops — TPU-first compute ops (attention and friends).

Beyond-parity scope: the reference has no attention code at all
(SURVEY.md §5 "Long-context / sequence parallelism: absent"), but a
TPU-native framework needs long-context attention as a first-class op —
it shapes the sharding design (ring/Ulysses sequence parallelism in
``apex_tpu.parallel``).
"""

from .attention import (blockwise_attention, mha_attention,  # noqa: F401
                        dot_product_attention)
from .flash_attention import flash_attention  # noqa: F401
from .ssd import causal_conv_silu, ssd_chunked, ssd_scan  # noqa: F401
from .short_conv import gated_short_conv  # noqa: F401
from .rope import apply_rope, qk_norm_rope, rope_angles  # noqa: F401
from .moe import latent_moe_layer, moe_layer  # noqa: F401
from . import losses  # noqa: F401
from .losses import (binary_cross_entropy,  # noqa: F401
                     binary_cross_entropy_with_logits)
