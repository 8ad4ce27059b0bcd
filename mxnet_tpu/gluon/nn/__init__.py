"""gluon.nn — neural network layers (parity: python/mxnet/gluon/nn/)."""
from ..block import Block, HybridBlock, SymbolBlock  # noqa: F401
from .activations import *  # noqa: F401,F403
from .basic_layers import *  # noqa: F401,F403
from .conv_layers import *  # noqa: F401,F403
from .moe import MOE_COUNTERS, DroplessMoE, MoEFFN  # noqa: F401
