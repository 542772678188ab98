from .bert import (
    BertConfig,
    BertEncoder,
    BertForPreTraining,
    BertForQuestionAnswering,
    BertModel,
    cross_entropy_ignore_index,
)
from .gpt2 import GPT2Config, GPT2LMHeadModel, GPT2Model, partition_specs
from .hybrid import HybridCausalLM, HybridLMConfig, HybridModel

__all__ = [
    "BertConfig",
    "BertEncoder",
    "BertForPreTraining",
    "BertForQuestionAnswering",
    "BertModel",
    "GPT2Config",
    "GPT2LMHeadModel",
    "GPT2Model",
    "HybridCausalLM",
    "HybridLMConfig",
    "HybridModel",
    "partition_specs",
    "cross_entropy_ignore_index",
]
