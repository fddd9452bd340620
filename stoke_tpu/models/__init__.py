"""Model library for examples/benchmarks.

The reference ships models only as example code (vendored torchvision ResNet,
examples/cifar10/model.py:19-293, and a BasicNN in README.md:100-102); here
they are first-class flax modules used by the examples, the benchmark, and
the driver entry point."""

from stoke_tpu.models.basic import BasicNN
from stoke_tpu.models.bert import (
    BERT_SIZES,
    BertBase,
    BertEncoder,
    BertForSequenceClassification,
    BertTiny,
    bert_tensor_parallel_rules,
    dense_attention,
)
from stoke_tpu.models.gpt import GPT, GPTBase, GPTTiny, causal_lm_loss

# The whole transformer family (BERT / GPT / ViT) shares TransformerBlock's
# parameter paths (attention/{qkv,out}, ff_{in,out}), so the Megatron-style
# column/row-parallel rules apply to every member; the aliases make intent
# explicit at call sites.
gpt_tensor_parallel_rules = bert_tensor_parallel_rules
vit_tensor_parallel_rules = bert_tensor_parallel_rules
from stoke_tpu.models.decoder import Decoder, DecoderConfig
from stoke_tpu.models.moe import (
    ExpertShareFFN,
    MoEFFN,
    MoETransformerBlock,
    moe_expert_parallel_rules,
)
from stoke_tpu.models.pipelined_lm import PipelinedLM, pipeline_parallel_rules
from stoke_tpu.models.vit import ViT, ViTBase, ViTTiny
from stoke_tpu.models.resnet import (
    ResNet,
    ResNet18,
    ResNet34,
    ResNet50,
    ResNet101,
    ResNet152,
)

__all__ = [
    "BasicNN",
    "BERT_SIZES",
    "BertBase",
    "BertEncoder",
    "BertForSequenceClassification",
    "BertTiny",
    "bert_tensor_parallel_rules",
    "gpt_tensor_parallel_rules",
    "vit_tensor_parallel_rules",
    "dense_attention",
    "GPT",
    "GPTBase",
    "GPTTiny",
    "causal_lm_loss",
    "Decoder",
    "DecoderConfig",
    "ExpertShareFFN",
    "MoEFFN",
    "MoETransformerBlock",
    "moe_expert_parallel_rules",
    "PipelinedLM",
    "pipeline_parallel_rules",
    "ViT",
    "ViTBase",
    "ViTTiny",
    "ResNet",
    "ResNet18",
    "ResNet34",
    "ResNet50",
    "ResNet101",
    "ResNet152",
]
