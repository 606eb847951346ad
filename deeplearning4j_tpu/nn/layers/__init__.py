"""Layer catalog (config+impl unified, JSON round-trippable).

Reference analog: org.deeplearning4j.nn.conf.layers.** +
org.deeplearning4j.nn.layers.** — see each module's docstring.
"""

from deeplearning4j_tpu.nn.layers.base import Layer, register_layer
from deeplearning4j_tpu.nn.layers.core import (
    DenseLayer, ActivationLayer, DropoutLayer, EmbeddingLayer,
    EmbeddingSequenceLayer, ElementWiseMultiplicationLayer,
)
from deeplearning4j_tpu.nn.layers.output import (
    OutputLayer, RnnOutputLayer, LossLayer, CenterLossOutputLayer, CnnLossLayer,
    LoopExitOutputLayer,
)
from deeplearning4j_tpu.nn.layers.conv import (
    ConvolutionLayer, Convolution1DLayer, Convolution3DLayer,
    Deconvolution2DLayer, SeparableConvolution2DLayer, DepthwiseConvolution2DLayer,
    SubsamplingLayer, Subsampling1DLayer, Upsampling2DLayer, Cropping2DLayer,
    ZeroPadding2DLayer, SpaceToDepthLayer, GlobalPoolingLayer,
    LocalResponseNormalizationLayer,
)
from deeplearning4j_tpu.nn.layers.norm import (
    BatchNormalizationLayer, LayerNormalizationLayer, RMSNormLayer,
)
from deeplearning4j_tpu.nn.layers.recurrent import (
    LSTMLayer, GravesLSTMLayer, GRULayer, SimpleRnnLayer, BidirectionalLayer,
    GravesBidirectionalLSTMLayer, LastTimeStepLayer, MaskZeroLayer,
    TimeDistributedLayer,
)
from deeplearning4j_tpu.nn.layers.objdetect import Yolo2OutputLayer
from deeplearning4j_tpu.nn.layers.variational import (
    AutoEncoderLayer, VariationalAutoencoderLayer,
)
from deeplearning4j_tpu.nn.layers.attention import (
    SelfAttentionLayer, LearnedSelfAttentionLayer, TransformerEncoderLayer,
    DecoderBlock,
)
from deeplearning4j_tpu.nn.layers.looped import LoopedStack
from deeplearning4j_tpu.nn.layers.experts import SparseExpertsLayer

__all__ = [
    "Layer", "register_layer",
    "DenseLayer", "ActivationLayer", "DropoutLayer", "EmbeddingLayer",
    "EmbeddingSequenceLayer", "ElementWiseMultiplicationLayer",
    "OutputLayer", "RnnOutputLayer", "LossLayer", "CenterLossOutputLayer",
    "CnnLossLayer", "LoopExitOutputLayer",
    "ConvolutionLayer", "Convolution1DLayer", "Convolution3DLayer",
    "Deconvolution2DLayer", "SeparableConvolution2DLayer",
    "DepthwiseConvolution2DLayer", "SubsamplingLayer", "Subsampling1DLayer",
    "Upsampling2DLayer", "Cropping2DLayer", "ZeroPadding2DLayer",
    "SpaceToDepthLayer", "GlobalPoolingLayer", "LocalResponseNormalizationLayer",
    "BatchNormalizationLayer", "LayerNormalizationLayer", "RMSNormLayer",
    "LSTMLayer", "GravesLSTMLayer", "GRULayer", "SimpleRnnLayer",
    "BidirectionalLayer", "GravesBidirectionalLSTMLayer", "LastTimeStepLayer",
    "MaskZeroLayer", "TimeDistributedLayer",
    "SelfAttentionLayer", "LearnedSelfAttentionLayer", "TransformerEncoderLayer",
    "DecoderBlock", "LoopedStack", "SparseExpertsLayer",
    "Yolo2OutputLayer", "AutoEncoderLayer", "VariationalAutoencoderLayer",
]
