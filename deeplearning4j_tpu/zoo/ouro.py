"""Ouro — a looped, weight-shared decoder language model.

Net-new: no reference analog. Token embedding, a ``LoopedStack`` of
``DecoderBlock``s (rotary positions, sandwich RMSNorm, gated MLP) applied
``ut_steps`` times with one set of weights and one final RMSNorm after every
pass, and ``LoopExitOutputLayer``: one untied head and one exit gate over every
pass's state, trained on the expected cross-entropy under the exit
distribution less ``beta`` times its entropy ("Scaling Latent Reasoning via
Looped Language Models", arXiv:2510.25741). Defaults are Ouro-2.6B's widths.
"""

from __future__ import annotations

import dataclasses

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    DecoderBlock, EmbeddingSequenceLayer, LoopedStack, LoopExitOutputLayer, RMSNormLayer,
)
from deeplearning4j_tpu.optimize.updaters import AdamW
from deeplearning4j_tpu.zoo.base import ZooModel


@dataclasses.dataclass
class Ouro(ZooModel):
    """Looped decoder for next-token training through ``fit``: int32 token
    ids ``[batch, seq]`` in, an int32 class index for every position as label."""

    vocab_size: int = 49152
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 16
    head_dim: int = 128
    d_ff: int = 5632
    ut_steps: int = 4
    rope_theta: float = 1e6
    rms_eps: float = 1e-6
    beta: float = 0.05
    lr: float = 3e-4
    dtype: str = "bf16"
    remat: bool = True

    def conf(self):
        block = DecoderBlock(d_model=self.d_model, n_heads=self.n_heads, head_dim=self.head_dim,
                             d_ff=self.d_ff, rope_theta=self.rope_theta, rms_eps=self.rms_eps)
        return (
            NeuralNetConfiguration.builder()
            .seed(self.seed)
            .updater(AdamW(lr=self.lr, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1))
            .data_type(self.dtype)
            .gradient_clipping(1.0)
            .gradient_checkpointing(self.remat)
            .list()
            .layer(EmbeddingSequenceLayer(n_in=self.vocab_size, n_out=self.d_model))
            .layer(LoopedStack(layers=(block,) * self.n_layers, times=self.ut_steps,
                               norm=RMSNormLayer(eps=self.rms_eps)))
            .layer(LoopExitOutputLayer(n_out=self.vocab_size, times=self.ut_steps,
                                       beta=self.beta))
            .set_input_type(InputType.recurrent(self.vocab_size, None))
            .build()
        )
