"""Layer implementations. Importing this package populates the JSON
subtype registry (reference analog: Jackson subtype scan)."""

from deeplearning4j_tpu.nn.layers.base import (  # noqa: F401
    LAYER_REGISTRY,
    FeedForwardLayerSpec,
    LayerSpec,
    layer_from_json,
    layer_to_json,
    register_layer,
)
from deeplearning4j_tpu.nn.layers.feedforward import (  # noqa: F401
    ActivationLayer,
    BaseOutputLayerSpec,
    DenseLayer,
    DropoutLayer,
    EmbeddingLayer,
    LossLayer,
    OutputLayer,
    SparseEmbeddingLayer,
)
from deeplearning4j_tpu.nn.layers.convolution import (  # noqa: F401
    BatchNormalization,
    ConvolutionLayer,
    LocalResponseNormalization,
    SubsamplingLayer,
)
from deeplearning4j_tpu.nn.layers.recurrent import (  # noqa: F401
    GravesBidirectionalLSTM,
    GravesLSTM,
    RnnOutputLayer,
)
from deeplearning4j_tpu.nn.layers.pretrain import (  # noqa: F401
    RBM,
    AutoEncoder,
)
from deeplearning4j_tpu.nn.layers.variational import (  # noqa: F401
    BernoulliReconstructionDistribution,
    CompositeReconstructionDistribution,
    ExponentialReconstructionDistribution,
    GaussianReconstructionDistribution,
    LossFunctionWrapper,
    ReconstructionDistribution,
    VariationalAutoencoder,
)
from deeplearning4j_tpu.nn.layers.attention import (  # noqa: F401
    LayerNormalization,
    MultiHeadSelfAttention,
    PositionalEncoding,
    TransformerBlock,
)
from deeplearning4j_tpu.nn.layers.moe import (  # noqa: F401
    MixtureOfExperts,
)
from deeplearning4j_tpu.nn.layers.decoder import (  # noqa: F401
    DecoderBlock,
    GatedFeedForward,
    GroupedQueryAttention,
    LatentAttention,
    LMOutputLayer,
    RMSNorm,
    RoutedExperts,
    TokenEmbedding,
    publish_routing_metrics,
)
from deeplearning4j_tpu.nn.layers.state_space import (  # noqa: F401
    StateSpaceMixer,
)
from deeplearning4j_tpu.nn.layers.linear_attention import (  # noqa: F401
    KimiDeltaAttention,
)
from deeplearning4j_tpu.nn.layers.short_conv import (  # noqa: F401
    GatedShortConv,
)
