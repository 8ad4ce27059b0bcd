"""Model zoo (parity: gluon/model_zoo + the GluonCV/GluonNLP families the
reference's baselines name: ResNet, BERT, GPT-2, transformer NMT, SSD)."""
from ..base import Registry

_REG = Registry("model")
register = _REG.register


def get_model(name, **kwargs):
    return _REG.create(name, **kwargs)


from .bert import (  # noqa: F401,E402
    BertConfig, BertForMaskedLM, BertForPretraining, BertModel,
    bert_base_config, bert_large_config)
from .gpt2 import (  # noqa: F401,E402
    GPT2Config, GPT2ForCausalLM, GPT2Model, gpt2_774m_config,
    gpt2_medium_config, gpt2_small_config, gpt2_xl_config)
from .falcon_h1 import (  # noqa: F401,E402
    FalconH1Config, FalconH1ForCausalLM, falcon_h1_34b_config)
from .nemotron_h import (  # noqa: F401,E402
    NemotronHConfig, NemotronHForCausalLM, nemotron3_super_120b_config)
from .kimi_linear import (  # noqa: F401,E402
    KimiLinearConfig, KimiLinearForCausalLM, kimi_linear_48b_config)
from .ouro import (  # noqa: F401,E402
    OuroConfig, OuroForCausalLM, ouro_2_6b_config)
from .trinity import (  # noqa: F401,E402
    TrinityConfig, TrinityForCausalLM, trinity_mini_config)
from .kv_cache import KVCache, PagedKVCache  # noqa: F401,E402
from .nmt import NMTConfig, TransformerNMT, nmt_base_config  # noqa: F401,E402
from . import vision  # noqa: F401,E402
