"""Model zoo (flax.linen).

One shared zoo replaces the reference's copy-pasted model definitions
(SURVEY.md §2.1 duplication note): MLP (`mnist_ddp_elastic.py:133-159`),
LeNet-style ConvNet (`mnist_horovod.py:9-25` ≡ `horovod_mnist_elastic.py:
16-32`), two-stage ResNet50 (`model_parallel_ResNet50.py:43-139`), and the
EmbeddingBag+Linear hybrid (`server_model_data_parallel.py:34-46`).
"""

from tpudist.models.beam import beam_search_generate
from tpudist.models.convnet import ConvNet
from tpudist.models.embedding import EmbeddingBagClassifier
from tpudist.models.generate import (
    greedy_generate,
    sample_generate,
    sp_generate,
    tp_generate,
    tp_sp_generate,
)
from tpudist.models.kv_pages import BlockPool, blocks_for
from tpudist.models.mlp import MLP
from tpudist.models.speculative import (
    AdaptiveDraftPolicy,
    adaptive_speculative_generate,
    sp_speculative_generate,
    speculative_generate,
    tp_sp_speculative_generate,
    tp_speculative_generate,
)
from tpudist.models.moe import MoEConfig, MoEMLP, MoETransformerLM
from tpudist.models.resnet import ResNet50, resnet50_stages
from tpudist.models.serving import (
    Completion,
    Request,
    RequestTiming,
    ServeLoop,
)
from tpudist.models.transformer import (
    LinearAttentionConfig,
    MLAConfig,
    TransformerConfig,
    TransformerLM,
    YarnScaling,
    repeat_kv,
    sdpa,
    stack_layer_params,
    unstack_layer_params,
)

__all__ = [
    "AdaptiveDraftPolicy",
    "BlockPool",
    "blocks_for",
    "Completion",
    "ConvNet",
    "Request",
    "RequestTiming",
    "ServeLoop",
    "adaptive_speculative_generate",
    "beam_search_generate",
    "EmbeddingBagClassifier",
    "LinearAttentionConfig",
    "MLAConfig",
    "MLP",
    "MoEConfig",
    "MoEMLP",
    "MoETransformerLM",
    "ResNet50",
    "TransformerConfig",
    "TransformerLM",
    "YarnScaling",
    "greedy_generate",
    "sample_generate",
    "sp_generate",
    "sp_speculative_generate",
    "speculative_generate",
    "tp_generate",
    "tp_sp_generate",
    "tp_sp_speculative_generate",
    "tp_speculative_generate",
    "resnet50_stages",
    "sdpa",
    "stack_layer_params",
    "unstack_layer_params",
]
