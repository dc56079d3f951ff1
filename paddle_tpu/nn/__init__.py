"""paddle_tpu.nn — Layers, containers, losses, functional.

TPU-native rebuild of the reference's paddle.fluid.dygraph layer API
(reference: python/paddle/fluid/dygraph/{layers,nn,container}.py).
"""
from .layer import Layer, functional_call, state_pytree, bind_state
from .container import Sequential, LayerList, ParameterList
from .layers import (
    Linear, Conv2D, Conv2DTranspose, Conv3D, MaxPool2D, AvgPool2D,
    AdaptiveAvgPool2D, Pool2D, BatchNorm, BatchNorm1D, BatchNorm2D,
    BatchNorm3D, SyncBatchNorm, LayerNorm, GroupNorm, InstanceNorm2D,
    RMSNorm, SpectralNorm, Embedding, Dropout, PRelu, BilinearTensorProduct, GRUUnit,
    Flatten, Upsample, Pad2D,
    ReLU, ReLU6, LeakyReLU, GELU, Sigmoid, Tanh, Softmax, LogSoftmax,
    Softplus, Hardswish, Hardsigmoid, Swish, Silu, Mish, ELU, SELU, Hardtanh,
)
from .loss import (CrossEntropyLoss, MSELoss, L1Loss, SmoothL1Loss, BCELoss,
                   BCEWithLogitsLoss, KLDivLoss, NLLLoss, MarginRankingLoss)
from .decode import (Decoder, BeamSearchDecoder, dynamic_decode,
                     gather_tree, DecodeHelper, TrainingHelper,
                     GreedyEmbeddingHelper, SamplingEmbeddingHelper,
                     BasicDecoder, basic_decode)
from .rnn import (RNNCellBase, SimpleRNNCell, LSTMCell, GRUCell, RNN, LSTM,
                  GRU, SimpleRNN, StaticRNN)
from . import functional
from . import functional as F
from .layers import NCE
from .layers import Conv3DTranspose, InstanceNorm, TreeConv

# paddle.nn 2.0-alpha alias tail (reference: python/paddle/nn/__init__.py)
from ..clip import (ClipGradByGlobalNorm as GradientClipByGlobalNorm,  # noqa
                    ClipGradByNorm as GradientClipByNorm,
                    ClipGradByValue as GradientClipByValue)
from ..fluid.layers_rnn import beam_search, beam_search_decode  # noqa: F401
from ..static import data  # noqa: F401
from ..ops import nn_ops as conv  # reference exports its conv module
from .layers import Upsample as UpSample  # noqa: F401 (2.0-alpha name)
from .layers import HSigmoid  # noqa: F401
from .moe import MoEFFN, RoutedMoE, GatedMLP, moe_aux_loss  # noqa: F401
from .hybrid import (Mamba2Mixer, GatedShortConv,  # noqa: F401
                     GroupedQueryAttention, MultiHeadLatentAttention,
                     SparseGroupedQueryAttention, MambaMixer,
                     DifferentialAttention, GatedMemoryUnit)
from ..fluid.dygraph import RowConv  # noqa: F401

# paddle.nn 1.x functional tails (reference: python/paddle/nn/
# {clip,control_flow}.py re-export the fluid twins at paddle.nn level)
from ..ops.math import clip  # noqa: F401,E402
from ..ops.control_flow import case, cond, while_loop  # noqa: F401,E402
