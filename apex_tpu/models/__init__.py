"""apex_tpu.models — benchmark model zoo (BASELINE.md configs)."""

from .resnet import (ResNet, ResNet18, ResNet34, ResNet50,  # noqa: F401
                     ResNet101, ResNet152, BottleneckBlock, BasicBlock)
from .bert import BertEncoder, bert_base, bert_tiny         # noqa: F401
from .dcgan import Generator, Discriminator                 # noqa: F401
from .gpt import GPT, gpt2_small, gpt_tiny, init_cache      # noqa: F401
from .granite_hybrid import GraniteHybrid, granite_hybrid_tiny  # noqa: F401
from .lfm2_moe import Lfm2Moe, lfm2_moe_tiny                # noqa: F401
from .nemotron_h import NemotronH, nemotron_h_tiny            # noqa: F401
from .smallthinker import SmallThinker, smallthinker_tiny          # noqa: F401
