"""SDAR — a sparse-expert decoder trained as a block-diffusion language model.

Net-new: no reference analog. ``BlockDiffusionNoiseLayer`` (the token table;
each step a rate ``t`` a block of ``block`` positions, each position masked
with probability ``t``, and ``x_t || x_0`` embedded: 2L positions), ``n_layers``
pre-norm ``DecoderBlock``s (``n_heads`` query heads over ``n_kv_heads``
key-value heads of ``head_dim`` with an RMSNorm on each head's q and k, rotary
positions 0 .. L - 1 in both halves, attention under the block-diffusion mask:
a noisy block sees itself both ways and the clean blocks before it, a clean
block the clean blocks up to itself) whose MLP is a ``SparseExpertsLayer`` (a
softmax router over ``n_experts``, ``top_k`` a token, weights renormalised, a
load-balancing term in the score), a final RMSNorm and an untied head over the
noised half, ``BlockDiffusionOutputLayer``: cross-entropy at the masked
positions weighted ``1 / t``. The mask id is ``mask_id``, by default the
table's last row; the noise's seed is drawn from the model's. Defaults
are SDAR-30B-A3B's published sizes; ``experts_held`` and a smaller
``vocab_size`` give one chip's share of an expert-parallel group.
"""

from __future__ import annotations

import dataclasses
from typing import Optional

from deeplearning4j_tpu.nn.conf.builders import NeuralNetConfiguration
from deeplearning4j_tpu.nn.conf.inputs import InputType
from deeplearning4j_tpu.nn.layers import (
    BlockDiffusionNoiseLayer, BlockDiffusionOutputLayer, DecoderBlock, RMSNormLayer,
    SparseExpertsLayer,
)
from deeplearning4j_tpu.optimize.schedules import WarmupCosineSchedule
from deeplearning4j_tpu.optimize.updaters import AdamW
from deeplearning4j_tpu.zoo.base import ZooModel


@dataclasses.dataclass
class SDAR(ZooModel):
    """Block-diffusion sparse-expert decoder for training through ``fit``:
    int32 clean token ids ``[batch, seq]`` in (``seq`` whole blocks), an int32
    class index for every position as label, scored where the step masked it."""

    vocab_size: int = 151936
    d_model: int = 2048
    n_layers: int = 48
    n_heads: int = 32
    n_kv_heads: int = 4
    head_dim: int = 128
    rope_theta: float = 1e6
    n_experts: int = 128
    top_k: int = 8
    d_expert: int = 768
    experts_held: Optional[tuple] = None        # (first, count); None: all
    aux_coef: float = 0.001
    rms_eps: float = 1e-6
    block: int = 4                  # positions a block of the diffusion
    t_min: float = 1e-3             # the least masking rate a block draws
    mask_id: Optional[int] = None   # the mask's row of the table; None: the last
    lr: float = 3e-4
    warmup: int = 0                 # steps of linear warm-up from 0 to ``lr``; 0: ``lr`` constant
    total_steps: int = 1_000_000    # where the cosine after a warm-up reaches 0
    dtype: str = "bf16"
    remat: bool = True

    def conf(self):
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
            .layer(BlockDiffusionNoiseLayer(n_in=self.vocab_size, n_out=self.d_model,
                                            block=self.block, t_min=self.t_min,
                                            mask_id=self.mask_id))
        )
        for _ in range(self.n_layers):
            builder = builder.layer(DecoderBlock(
                d_model=self.d_model, n_heads=self.n_heads, n_kv_heads=self.n_kv_heads,
                head_dim=self.head_dim, rope_theta=self.rope_theta, rms_eps=self.rms_eps,
                norm="pre", qk_norm=True, block_diffusion=self.block, mlp=experts))
        return (
            builder
            .layer(RMSNormLayer(eps=self.rms_eps))
            .layer(BlockDiffusionOutputLayer(n_out=self.vocab_size, has_bias=False,
                                             loss="sparsemcxent"))
            .set_input_type(InputType.recurrent(self.vocab_size, None))
            .build()
        )
