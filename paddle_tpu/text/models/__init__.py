from .bert import BertConfig, BertForPretraining, BertModel  # noqa: F401
from .dots3_note import Dots3NoteConfig, Dots3NoteForCausalLM  # noqa: F401
from .ernie_moe import ErnieMoEConfig, ErnieMoEForCausalLM  # noqa: F401
from .falcon_h1 import FalconH1Config, FalconH1ForCausalLM  # noqa: F401
from .k_exaone import KExaoneConfig, KExaoneForCausalLM  # noqa: F401
from .llama import (LlamaConfig, LlamaDecoderLayer,  # noqa: F401
                    LlamaForCausalLM, LlamaModel, build_llama_pipe,
                    force_tp_layers, llama_flops_per_token)
from .solar_open2 import SolarOpen2Config, SolarOpen2ForCausalLM  # noqa: F401
