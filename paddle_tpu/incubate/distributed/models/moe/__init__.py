from .gate import (BaseGate, GShardGate, NaiveGate,  # noqa: F401
                   SigmoidTopKGate, SwitchGate, topk_gating)
from .moe_layer import GroupedExpertsFFN, MoELayer  # noqa: F401
