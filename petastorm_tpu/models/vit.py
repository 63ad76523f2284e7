"""Vision Transformer — the second vision flagship next to ResNet-50.

No reference equivalent (the reference ships no models, SURVEY.md §2.6);
this exists to prove the image pipeline end to end on a transformer
backbone: uint8 batches from ``petastorm_tpu.jax.DataLoader``, on-device
``petastorm_tpu.jax.augment``, encoder blocks shared with
``models.transformer`` (same ``Block``/``Attention`` modules with
``causal=False``), so the Megatron TP rules and FSDP composition apply
unchanged.

TPU design notes:
* Patchify is a stride-``patch`` conv — one big MXU matmul per image, no
  gather/reshape shuffle on the VPU.
* Everything runs bf16 on the MXU (``dtype``); norms/softmax stats fp32.
* ``pool='mean'`` (default) global-average-pools patch tokens — no class
  token means the sequence length stays a multiple of the patch grid,
  which keeps flash-attention block tiling clean.
"""

from typing import Any, Callable

import flax.linen as nn
import jax.numpy as jnp

from petastorm_tpu.models.transformer import (REMAT_POLICY, Block, RMSNorm,
                                              megatron_spec_fn,
                                              param_shardings)
from petastorm_tpu.ops import flash_attention

__all__ = ['ViT', 'param_shardings', 'megatron_spec_fn']


class ViT(nn.Module):
    """images [batch, H, W, C] float/bf16 -> logits [batch, num_classes]."""

    num_classes: int
    patch_size: int = 16
    d_model: int = 384
    num_heads: int = 6
    num_layers: int = 12
    d_ff: int = 1536
    dtype: Any = jnp.bfloat16
    attn_fn: Callable = flash_attention
    pool: str = 'mean'            # 'mean' | 'cls'
    remat: bool = False

    @nn.compact
    def __call__(self, images):
        if images.ndim != 4:
            raise ValueError('expected [batch, H, W, C], got %r'
                             % (images.shape,))
        h, w = images.shape[1], images.shape[2]
        if h % self.patch_size or w % self.patch_size:
            raise ValueError('image %dx%d not divisible by patch_size %d'
                             % (h, w, self.patch_size))
        if self.pool not in ('mean', 'cls'):
            raise ValueError("pool must be 'mean' or 'cls', got %r"
                             % (self.pool,))

        x = nn.Conv(self.d_model, (self.patch_size, self.patch_size),
                    strides=(self.patch_size, self.patch_size),
                    dtype=self.dtype, name='patch_embed')(
                        images.astype(self.dtype))
        b = x.shape[0]
        x = x.reshape(b, -1, self.d_model)      # [b, n_patches, d]
        n = x.shape[1]

        if self.pool == 'cls':
            cls = self.param('cls_token', nn.initializers.zeros,
                             (1, 1, self.d_model))
            x = jnp.concatenate(
                [jnp.broadcast_to(cls, (b, 1, self.d_model)).astype(x.dtype),
                 x], axis=1)
            n += 1
        pos = self.param('pos_embed',
                         nn.initializers.normal(stddev=0.02),
                         (1, n, self.d_model))
        x = x + pos.astype(x.dtype)

        block = nn.remat(Block, policy=REMAT_POLICY) if self.remat else Block
        for i in range(self.num_layers):
            x = block(self.num_heads, self.d_ff, self.dtype, self.attn_fn,
                      causal=False, name='block_%d' % i)(x)
        x = RMSNorm(name='ln_f')(x)
        x = x[:, 0] if self.pool == 'cls' else x.mean(axis=1)
        return nn.Dense(self.num_classes, dtype=jnp.float32,
                        name='head')(x.astype(jnp.float32))
