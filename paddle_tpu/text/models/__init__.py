from .bert import (BertModel, BertForSequenceClassification,  # noqa: F401
                   BertForPretraining, ErnieModel,
                   ErnieForSequenceClassification, ErnieForPretraining,
                   ernie_1_0)
from .gpt import GPTModel, GPTForCausalLM, GPTConfig  # noqa: F401
from .olmo_hybrid import (OlmoHybridConfig, OlmoHybridModel,  # noqa: F401
                          OlmoHybridForCausalLM)
from .kimi_linear import (KimiLinearConfig, KimiLinearModel,  # noqa: F401
                          KimiLinearForCausalLM)
