"""paddle_tpu.nn (ref: python/paddle/nn/__init__.py layer zoo)."""
from __future__ import annotations

from .layer import Layer, Parameter  # noqa: F401
from .param_attr import ParamAttr  # noqa: F401
from . import initializer  # noqa: F401
from . import functional  # noqa: F401
from . import quant  # noqa: F401  (weight-only quantization)
from .clip import (  # noqa: F401
    ClipGradByValue, ClipGradByNorm, ClipGradByGlobalNorm, clip_grad_norm_,
)

from . import utils  # noqa: F401
from .layers.common import (  # noqa: F401
    Linear, Identity, Dropout, Dropout2D, Dropout3D, AlphaDropout, Embedding,
    Fold,
    Flatten, Upsample, UpsamplingBilinear2D, UpsamplingNearest2D, Pad1D,
    Pad2D, Pad3D, ZeroPad2D, Bilinear, CosineSimilarity, PairwiseDistance,
    PixelShuffle, PixelUnshuffle, ChannelShuffle, Unfold,
)
from .layers.moe import (  # noqa: F401
    SparseExpertFFN, SwiGLU, observe_expert_load)
from .layers.rope import rope_tables, yarn_inv_freq  # noqa: F401
from .layers.cca import CompressedConvAttention, ResidualScale  # noqa: F401
from .layers.gdn import GatedDeltaNet, ZeroCenteredRMSNorm  # noqa: F401
from .layers.conv import (  # noqa: F401
    Conv1D, Conv2D, Conv3D, Conv2DTranspose, Conv1DTranspose,
    Conv3DTranspose,
)
from .layers.norm import (  # noqa: F401
    LayerNorm, RMSNorm, BatchNorm, BatchNorm1D, BatchNorm2D, BatchNorm3D,
    SyncBatchNorm, GroupNorm, InstanceNorm1D, InstanceNorm2D, InstanceNorm3D,
    LocalResponseNorm, SpectralNorm,
)
from .layers.activation import (  # noqa: F401
    ReLU, ReLU6, GELU, Sigmoid, Tanh, Softmax, LogSoftmax, LeakyReLU, PReLU,
    ELU, SELU, CELU, Silu, Swish, Mish, Hardswish, Hardsigmoid, Hardtanh,
    Hardshrink, Softshrink, Tanhshrink, Softplus, Softsign, ThresholdedReLU,
    LogSigmoid, Maxout, GLU,
    SiLU, Softmax2D,
)
from .layers.pooling import (  # noqa: F401
    MaxPool1D, MaxPool2D, MaxPool3D, AvgPool1D, AvgPool2D, AvgPool3D,
    AdaptiveAvgPool1D, AdaptiveAvgPool2D, AdaptiveMaxPool2D,
    AdaptiveAvgPool3D, AdaptiveMaxPool1D, AdaptiveMaxPool3D,
    MaxUnPool2D,
)
from .layers.loss import (  # noqa: F401
    CrossEntropyLoss, MSELoss, L1Loss, SmoothL1Loss, NLLLoss, BCELoss,
    BCEWithLogitsLoss, KLDivLoss, MarginRankingLoss, HingeEmbeddingLoss,
    CosineEmbeddingLoss, TripletMarginLoss,
)
from .layers.container import (  # noqa: F401
    Sequential, LayerList, ParameterList, LayerDict,
)
from .layers.transformer import (  # noqa: F401
    MultiHeadAttention, TransformerEncoderLayer, TransformerEncoder,
    TransformerDecoderLayer, TransformerDecoder, Transformer,
)
from .layers.rnn import (  # noqa: F401
    LSTM, GRU, SimpleRNN, LSTMCell, GRUCell,
    RNN, BiRNN, RNNCellBase, SimpleRNNCell,
)


def __getattr__(name):
    """`nn.MultiHeadLatentAttention`, imported when first asked for (its
    module reaches the fused functional ops, which this package's other
    layers do not need at import)."""
    if name == "MultiHeadLatentAttention":
        from .layers.mla import MultiHeadLatentAttention
        return MultiHeadLatentAttention
    raise AttributeError(f"module {__name__!r} has no attribute {name!r}")
