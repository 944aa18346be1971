"""Language model zoo (reference capability: PaddleNLP model family on the
fleet mpu layers; BASELINE.json configs 3-4)."""
from .gpt import (  # noqa: F401
    GPTConfig,
    GPTDecoderLayer,
    GPTForCausalLM,
    GPTModel,
    GPTPretrainingCriterion,
    gpt_1p3b,
    gpt_medium,
    gpt_small,
    gpt_tiny,
)

from .bert import (  # noqa: F401
    BertConfig,
    BertForPretraining,
    BertForSequenceClassification,
    BertModel,
    BertPretrainingCriterion,
    ErnieForPretraining,
    ErnieModel,
    bert_base,
    bert_tiny,
    ernie_3_base,
)

from .laguna import LagunaConfig, LagunaForCausalLM, laguna_tiny  # noqa: F401,E402
from .serving_protocol import CacheKind  # noqa: F401,E402

__all__ = [
    "LagunaConfig", "LagunaForCausalLM", "laguna_tiny", "CacheKind",
    "GPTConfig", "GPTDecoderLayer", "GPTModel", "GPTForCausalLM",
    "GPTPretrainingCriterion", "gpt_tiny", "gpt_small", "gpt_medium",
    "gpt_1p3b",
    "BertConfig", "BertModel", "BertForPretraining",
    "BertPretrainingCriterion", "BertForSequenceClassification",
    "ErnieModel", "ErnieForPretraining", "bert_tiny", "bert_base",
    "ernie_3_base",
]
