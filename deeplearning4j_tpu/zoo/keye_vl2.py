"""Keye-VL-2.0's language model — a sparse-expert decoder whose attention
chooses its keys with a learned indexer.

Net-new: no reference analog. Token embedding, ``n_layers`` pre-norm
``DecoderBlock``s whose first half is a ``SparseAttentionLayer`` (``n_heads``
query heads over ``n_kv_heads`` key-value heads of ``head_dim`` with an RMSNorm
on each head's q and k, M-RoPE over ``mrope_sections``, and DeepSeek Sparse
Attention's lightning indexer: ``index_heads`` heads of ``index_head_dim``
choose ``topk`` keys a query, and learn from their own loss against the
attention's weights) and whose second half is a ``SparseExpertsLayer`` (a
softmax router over ``n_experts``, ``top_k`` a token, weights renormalised, a
load-balancing term in the score), a final RMSNorm and an untied head with
softmax cross-entropy at every position. Defaults are Keye-VL-2.0-30B-A3B's
published sizes; ``experts_held`` and a smaller ``vocab_size`` give one chip's
share of an expert-parallel group. Text only: every token's three M-RoPE
positions are its one position.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    DecoderBlock, EmbeddingSequenceLayer, RMSNormLayer, RnnOutputLayer, SparseAttentionLayer,
    SparseExpertsLayer,
)
from deeplearning4j_tpu.optimize.schedules import WarmupCosineSchedule
from deeplearning4j_tpu.optimize.updaters import AdamW
from deeplearning4j_tpu.zoo.base import ZooModel


@dataclasses.dataclass
class KeyeVL2(ZooModel):
    """Learned-sparse-attention sparse-expert decoder for next-token training
    through ``fit``: int32 token ids ``[batch, seq]`` in, an int32 class index
    for every position as label."""

    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e7
    mrope_sections: tuple = (16, 24, 24)
    index_heads: int = 16
    index_head_dim: int = 64
    index_rope_dim: int = 32
    topk: int = 2048
    n_experts: int = 128
    top_k: int = 8
    d_expert: int = 768
    experts_held: Optional[tuple] = None        # (first, count); None: all
    aux_coef: float = 0.001
    rms_eps: float = 1e-6
    lr: float = 3e-4
    warmup: int = 0                 # steps of linear warm-up from 0 to ``lr``; 0: ``lr`` constant
    total_steps: int = 1_000_000    # where the cosine after a warm-up reaches 0
    dtype: str = "bf16"
    remat: bool = True

    def conf(self):
        mixer = SparseAttentionLayer(
            d_model=self.d_model, n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
            head_dim=self.head_dim, rope_theta=self.rope_theta,
            mrope_sections=tuple(self.mrope_sections), norm_eps=self.rms_eps,
            index_heads=self.index_heads, index_head_dim=self.index_head_dim,
            index_rope_dim=self.index_rope_dim, topk=self.topk)
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
        for _ in range(self.n_layers):
            builder = builder.layer(DecoderBlock(d_model=self.d_model, rms_eps=self.rms_eps,
                                                 norm="pre", mixer=mixer, mlp=experts))
        return (
            builder
            .layer(RMSNormLayer(eps=self.rms_eps))
            .layer(RnnOutputLayer(n_out=self.vocab_size, has_bias=False, loss="sparsemcxent"))
            .set_input_type(InputType.recurrent(self.vocab_size, None))
            .build()
        )
