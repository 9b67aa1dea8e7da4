from .gate import (BaseGate, CapacityFreeGate, GroupLimitedGate,  # noqa: F401
                   GShardGate, NaiveGate, SigmoidTopKGate, SwitchGate,
                   topk_gating)
from .moe_layer import GroupedExpertsFFN, MoELayer, moe_tick_stats  # noqa: F401
