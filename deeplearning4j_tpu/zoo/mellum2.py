"""Mellum2 — a sparse-expert decoder language model with grouped key-value
heads and window and full attention mixed.

Net-new: no reference analog. Token embedding, ``n_layers`` pre-norm
``DecoderBlock``s whose MLP is a ``SparseExpertsLayer`` (a router over
``n_experts``, ``top_k`` a token, weights renormalised, a load-balancing term
in the score), ``n_heads`` query heads over ``n_kv_heads`` key-value heads with
an RMSNorm on each head's q and k, by ``layer_types`` either a causal window
with plain rotary positions or full causal attention with YaRN-scaled ones, a
final RMSNorm and an untied head with softmax cross-entropy at every position.
Defaults are Mellum2-12B-A2.5B's published sizes; ``experts_held`` and a
smaller ``vocab_size`` give one chip's share of an expert-parallel group.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    DecoderBlock, EmbeddingSequenceLayer, RMSNormLayer, RnnOutputLayer, SparseExpertsLayer,
)
from deeplearning4j_tpu.optimize.schedules import WarmupCosineSchedule
from deeplearning4j_tpu.optimize.updaters import AdamW
from deeplearning4j_tpu.zoo.base import ZooModel

PERIOD = ("sliding_attention",) * 3 + ("full_attention",)


@dataclasses.dataclass
class Mellum2(ZooModel):
    """Sparse-expert decoder for next-token training through ``fit``: int32
    token ids ``[batch, seq]`` in, an int32 class index for every position as
    label."""

    vocab_size: int = 98304
    d_model: int = 2304
    n_layers: int = 28
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    n_experts: int = 64
    top_k: int = 8
    d_expert: int = 896
    experts_held: Optional[tuple] = None        # (first, count); None: all
    aux_coef: float = 0.001
    layer_types: Optional[tuple] = None         # a configuration's own list; None: the published period, repeated
    window: int = 1024
    rope_theta: float = 5e5
    rope_yarn: tuple = (16.0, 8192, 32.0, 1.0, 1.2772588722239782)    # the full layers'
    rms_eps: float = 1e-6
    lr: float = 3e-4
    warmup: int = 0                 # steps of linear warm-up from 0 to ``lr``; 0: ``lr`` constant
    total_steps: int = 1_000_000    # where the cosine after a warm-up reaches 0
    dtype: str = "bf16"
    remat: bool = True

    def conf(self):
        types = tuple(self.layer_types or (PERIOD * -(-self.n_layers // len(PERIOD)))[:self.n_layers])
        if len(types) != self.n_layers or set(types) - set(PERIOD):
            raise ValueError(f"layer_types {types}: {self.n_layers} of {sorted(set(PERIOD))}")
        held = None if self.experts_held is None else tuple(self.experts_held)
        experts = SparseExpertsLayer(n_experts=self.n_experts, top_k=self.top_k,
                                     d_expert=self.d_expert, experts_held=held,
                                     aux_coef=self.aux_coef)
        lr = self.lr if not self.warmup else WarmupCosineSchedule(
            peak_value=self.lr, warmup_steps=self.warmup, total_steps=self.total_steps)
        builder = (
            NeuralNetConfiguration.builder()
            .seed(self.seed)
            .updater(AdamW(lr=lr, beta1=0.9, beta2=0.95, eps=1e-8, weight_decay=0.1))
            .data_type(self.dtype)
            .gradient_clipping(1.0)
            .gradient_checkpointing(self.remat)
            .list()
            .layer(EmbeddingSequenceLayer(n_in=self.vocab_size, n_out=self.d_model))
        )
        for kind in types:
            sliding = kind == "sliding_attention"
            builder = builder.layer(DecoderBlock(
                d_model=self.d_model, n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim, rope_theta=self.rope_theta, rms_eps=self.rms_eps,
                norm="pre", qk_norm=True, window=self.window if sliding else None,
                rope_yarn=None if sliding else tuple(self.rope_yarn), mlp=experts))
        return (
            builder
            .layer(RMSNormLayer(eps=self.rms_eps))
            .layer(RnnOutputLayer(n_out=self.vocab_size, has_bias=False, loss="sparsemcxent"))
            .set_input_type(InputType.recurrent(self.vocab_size, None))
            .build()
        )
