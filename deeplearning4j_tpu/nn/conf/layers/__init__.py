"""Layer configuration + functional implementation classes.

Unlike the reference, which splits layer *config* (nn/conf/layers/*.java) from layer
*implementation* (nn/layers/**), the TPU-native design merges them: each dataclass is a
JSON-serializable config AND owns pure functions ``init_params`` / ``apply`` /
``output_type``. Backprop comes from JAX autodiff instead of hand-written
``backpropGradient`` — correctness is enforced by the same numeric gradient-check
strategy the reference uses (reference gradientcheck/GradientCheckUtil.java:62).
"""
from deeplearning4j_tpu.nn.conf.layers.base import Layer, FeedForwardLayer, PretrainLayer
from deeplearning4j_tpu.nn.conf.layers.feedforward import (
    DenseLayer, OutputLayer, LossLayer, ActivationLayer, DropoutLayer,
    EmbeddingLayer, AutoEncoder, RBM,
)
from deeplearning4j_tpu.nn.conf.layers.convolutional import (
    ConvolutionLayer, SubsamplingLayer, Upsampling2D, ZeroPaddingLayer, GlobalPoolingLayer,
)
from deeplearning4j_tpu.nn.conf.layers.normalization import (
    BatchNormalization, LocalResponseNormalization,
)
from deeplearning4j_tpu.nn.conf.layers.recurrent import (
    GravesLSTM, LSTM, GravesBidirectionalLSTM, RnnOutputLayer,
)
from deeplearning4j_tpu.nn.conf.layers.variational import (
    BernoulliReconstructionDistribution, CompositeReconstructionDistribution,
    ExponentialReconstructionDistribution, GaussianReconstructionDistribution,
    ReconstructionDistribution, VariationalAutoencoder,
)
from deeplearning4j_tpu.nn.conf.layers.attention import (
    SelfAttentionLayer, TransformerBlock,
)
# imported for registration side effects too: a saved MoE model zip must
# restore without the caller having imported the module first
from deeplearning4j_tpu.nn.conf.layers.moe import MoELayer, MoETransformerBlock
from deeplearning4j_tpu.nn.conf.layers.decoder import DecoderBlock, RMSNormLayer

__all__ = [
    "Layer", "FeedForwardLayer", "PretrainLayer",
    "DenseLayer", "OutputLayer", "LossLayer", "ActivationLayer", "DropoutLayer",
    "EmbeddingLayer", "AutoEncoder", "RBM",
    "ConvolutionLayer", "SubsamplingLayer", "Upsampling2D", "ZeroPaddingLayer",
    "GlobalPoolingLayer",
    "BatchNormalization", "LocalResponseNormalization",
    "GravesLSTM", "LSTM", "GravesBidirectionalLSTM", "RnnOutputLayer",
    "VariationalAutoencoder", "SelfAttentionLayer", "TransformerBlock", "MoELayer", "MoETransformerBlock",
    "DecoderBlock", "RMSNormLayer",
]
