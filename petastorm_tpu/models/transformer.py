"""Long-context Transformer LM — the sequence-parallel flagship.

No reference equivalent (the reference ships no models; SURVEY.md §2.6 —
its examples train third-party torch/TF models).  This model exists to
prove the framework's long-context plane end to end: data from
``petastorm_tpu.jax.DataLoader``, attention from
``petastorm_tpu.ops.flash_attention`` (single device) or
``petastorm_tpu.parallel.ring/ulysses`` (sequence-sharded), parameters
sharded Megatron-style over a ``model`` mesh axis.

TPU design notes:
* All matmuls run in bfloat16 on the MXU (``dtype``); accumulation and the
  softmax/norm stats stay fp32.
* ``attn_fn`` is injected, not hard-coded: the module computes q/k/v
  ``[batch, seq, heads, head_dim]`` and delegates — so one model definition
  serves dense oracle, Pallas flash, ring (seq axis over ICI ring via
  ppermute), and Ulysses (all-to-all) without touching the module.
* A layer is a token mixer and a feed-forward, each of a KIND: ``Block``
  takes ``mixer`` ('attention' | 'conv', the gated short convolution | 'kda',
  the gated delta rule with per-channel decays: :class:`DeltaAttention` |
  'mla', latent attention without rotation: :class:`LatentAttention`) and
  ``ffn`` ('gelu' | 'swiglu' | 'moe', one chip's share of a top-k expert
  layer with, where the model has one, its shared expert:
  ``models.moe.moe_share_apply``), and ``TransformerLM`` a ``layer_types``
  pattern with ``num_dense_layers`` leading dense ones — how hybrids (conv,
  conv, attention, conv, or three 'kda' to one 'mla'; dense then experts)
  are spelled.  The defaults are the classic attention + GELU block with
  the tied head, the parameter tree unchanged.  In a packed row
  (``segment_ids``) no mixer reaches across a document boundary.
* ``param_shardings`` maps the param pytree onto a mesh: attention/MLP
  input projections shard their *output* features over ``model``; output
  projections shard their *input* features — the Megatron sandwich, which
  leaves XLA exactly one all-reduce per block per direction.
"""

import functools
from typing import Any, Callable

import flax.linen as nn
import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from petastorm_tpu.ops import flash_attention


def rope_cos_sin(positions, head_dim, base=10000.0):
    """RoPE rotation tables for ``positions`` [b, s]: cos/sin, each
    [b, s, 1, head_dim/2] — compute once, rotate q AND k with them."""
    if head_dim % 2:
        raise ValueError('RoPE needs an even head_dim, got %d' % head_dim)
    half = head_dim // 2
    freqs = base ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[:, :, None].astype(jnp.float32) * freqs  # [b, s, half]
    return (jnp.cos(angles)[:, :, None, :],
            jnp.sin(angles)[:, :, None, :])


def rope(x, positions=None, base=10000.0, cos_sin=None):
    """Rotary position embedding (GPT-NeoX split-half convention).

    ``x``: [batch, seq, heads, head_dim]; ``positions``: [batch, seq] (or
    pass a precomputed ``cos_sin`` from :func:`rope_cos_sin`).  Rotation
    happens BEFORE the attention delegation, so every attn_fn (dense,
    flash, ring, Ulysses — packed or not) inherits it untouched; with
    ``packing`` positions that restart per document, each packed document
    is rotated as if it started at 0.
    """
    if cos_sin is None:
        cos_sin = rope_cos_sin(positions, x.shape[-1], base)
    cos, sin = cos_sin
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rotated = jnp.concatenate([x1 * cos - x2 * sin,
                               x1 * sin + x2 * cos], axis=-1)
    return rotated.astype(x.dtype)


class RMSNorm(nn.Module):
    eps: float = 1e-6

    @nn.compact
    def __call__(self, x):
        scale = self.param('scale', nn.initializers.ones, (x.shape[-1],))
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        return (x * jax.lax.rsqrt(var + self.eps)).astype(x.dtype) * scale


class Attention(nn.Module):
    num_heads: int
    dtype: Any = jnp.bfloat16
    attn_fn: Callable = flash_attention
    causal: bool = True  # False for encoder use (e.g. models.vit)
    decode: bool = False  # autoregressive KV-cache mode (see models.decoding)
    max_decode_len: int = 2048
    #: Grouped-query attention: K/V projected to this many heads (must
    #: divide num_heads); each K/V head serves num_heads//num_kv_heads
    #: query heads.  The decode cache stores only the KV heads — the
    #: long-context memory win.  None = classic MHA (fused qkv projection,
    #: parameter tree unchanged).
    num_kv_heads: Any = None
    #: 'rope' rotates q/k by position before delegation (cached keys are
    #: stored rotated — standard practice); None = positions handled
    #: upstream (learned table in TransformerLM).
    pos_mode: Any = None
    rope_base: float = 10000.0
    #: RMS-normalise q and k over the head dimension (a learned scale each,
    #: ``q_norm`` / ``k_norm``) before the rotation, as LFM2 and others do.
    qk_norm: bool = False
    norm_eps: float = 1e-6
    use_bias: bool = True

    @nn.compact
    def __call__(self, x, positions=None, segment_ids=None):
        """``segment_ids`` ([batch, seq], 0 = padding) keeps attention inside
        each packed document; it is handed to ``attn_fn`` by keyword."""
        d_model = x.shape[-1]
        if d_model % self.num_heads:
            raise ValueError('d_model %d not divisible by %d heads'
                             % (d_model, self.num_heads))
        head_dim = d_model // self.num_heads
        if self.num_kv_heads is None:
            qkv = nn.DenseGeneral((3, self.num_heads, head_dim), axis=-1,
                                  dtype=self.dtype, use_bias=self.use_bias,
                                  name='qkv')(x)
            q, k, v = jnp.moveaxis(qkv, -3, 0)  # each [b, s, h, hd]
        else:
            if self.num_heads % self.num_kv_heads:
                raise ValueError('num_heads %d not divisible by num_kv_heads %d'
                                 % (self.num_heads, self.num_kv_heads))
            q = nn.DenseGeneral((self.num_heads, head_dim), axis=-1,
                                dtype=self.dtype, use_bias=self.use_bias,
                                name='q')(x)
            kv = nn.DenseGeneral((2, self.num_kv_heads, head_dim), axis=-1,
                                 dtype=self.dtype, use_bias=self.use_bias,
                                 name='kv')(x)
            k, v = jnp.moveaxis(kv, -3, 0)      # [b, s, h_kv, hd]
        if self.qk_norm:
            # the float32 scale promotes: back to the compute dtype, or the
            # attention kernels run in float32
            q = RMSNorm(self.norm_eps, name='q_norm')(q).astype(self.dtype)
            k = RMSNorm(self.norm_eps, name='k_norm')(k).astype(self.dtype)
        if self.pos_mode == 'rope':
            if positions is None:
                if self.decode:
                    # arange(seq) would rotate every 1-token step at
                    # position 0 — silently wrong; demand real positions.
                    raise ValueError('decode mode with RoPE requires '
                                     'explicit positions')
                positions = jnp.broadcast_to(jnp.arange(x.shape[1]),
                                             x.shape[:2])
            cs = rope_cos_sin(positions, q.shape[-1],
                              self.rope_base)  # once for q AND k
            q = rope(q, cos_sin=cs)
            k = rope(k, cos_sin=cs)
        if self.decode:
            out = self._decode_step(q, k, v)
        else:
            k, v = self._expand_kv(k, v)
            packed = {} if segment_ids is None else {'segment_ids': segment_ids}
            out = self.attn_fn(q, k, v, causal=self.causal, **packed)
        return nn.DenseGeneral(d_model, axis=(-2, -1), dtype=self.dtype,
                               use_bias=self.use_bias, name='out')(out)

    def _expand_kv(self, k, v):
        """Broadcast KV heads to the query head count (GQA no-op for MHA)."""
        if self.num_kv_heads is None or self.num_kv_heads == self.num_heads:
            return k, v
        g = self.num_heads // self.num_kv_heads
        return jnp.repeat(k, g, axis=2), jnp.repeat(v, g, axis=2)

    def _decode_step(self, q, k, v):
        """Attention against a fixed-size KV cache (incremental decoding).

        XLA-friendly: the cache is a STATIC ``[b, max_decode_len, h, hd]``
        buffer updated in place with ``dynamic_update_slice``; one-token
        queries attend the whole buffer with future positions masked — no
        shape ever depends on the step index, so the generate loop compiles
        once (``lax.scan`` in ``models.decoding``).  A multi-token call on a
        FRESH cache (index 0) is the classic prefill: it writes the whole
        prompt's K/V and runs ordinary causal attention over just the prompt
        — one MXU-batched forward instead of L sequential steps.  On a WARM
        cache (index > 0 — chunked prefill, cache reuse) the chunk instead
        attends the full cache buffer with absolute-position causal masking,
        so cached history is honored; ``lax.cond`` picks the branch at run
        time without breaking the compile-once property.  Flax init never
        mutates the cache (``is_initializing`` guard), so a freshly
        initialized cache is all-zeros with index 0.
        """
        b, seq, h, hd = q.shape
        h_kv = k.shape[2]   # < h under GQA: the cache memory win
        cache_k = self.variable('cache', 'key', jnp.zeros,
                                (b, self.max_decode_len, h_kv, hd), self.dtype)
        cache_v = self.variable('cache', 'value', jnp.zeros,
                                (b, self.max_decode_len, h_kv, hd), self.dtype)
        index = self.variable('cache', 'index', jnp.zeros, (), jnp.int32)
        i = index.value
        if not self.is_initializing():
            cache_k.value = jax.lax.dynamic_update_slice(
                cache_k.value, k.astype(self.dtype), (0, i, 0, 0))
            cache_v.value = jax.lax.dynamic_update_slice(
                cache_v.value, v.astype(self.dtype), (0, i, 0, 0))
            index.value = i + seq
        q_pos = i + jnp.arange(seq)
        if seq > 1:
            def fresh_prefill(q, k, v):
                # fresh cache: causal attention over just the prompt —
                # cheaper than attending the (empty) full buffer
                k, v = self._expand_kv(k, v)
                return self.attn_fn(q, k, v, causal=True)

            def warm_prefill(q, k, v):
                return self._attend_cache(q, cache_k.value, cache_v.value,
                                          q_pos)
            return jax.lax.cond(i == 0, fresh_prefill, warm_prefill, q, k, v)
        return self._attend_cache(q, cache_k.value, cache_v.value, q_pos)

    def _attend_cache(self, q, ck, cv, q_pos):
        """Attend the static cache buffer at absolute query positions.

        Grouped einsum against the UNEXPANDED cache: per-step HBM reads
        stay at h_kv heads (the actual GQA bandwidth win), accumulation
        in fp32 via preferred_element_type — no repeated/casted copies.
        """
        b, seq, h, hd = q.shape
        h_kv = ck.shape[2]
        g = h // h_kv
        q_g = q.astype(jnp.float32).reshape(b, seq, h_kv, g, hd)
        scores = jnp.einsum('bqkgd,blkd->bkgql', q_g, ck,
                            preferred_element_type=jnp.float32) * hd ** -0.5
        mask = jnp.arange(self.max_decode_len)[None, :] <= q_pos[:, None]
        from petastorm_tpu.parallel.ring_attention import NEG_INF
        scores = jnp.where(mask[None, None, None, :, :], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1)
        out = jnp.einsum('bkgql,blkd->bqkgd', probs, cv,
                         preferred_element_type=jnp.float32)
        return out.reshape(b, seq, h, hd).astype(q.dtype)


def causal_taps(u, taps, segment_ids=None):
    """``c_t = sum_k taps[k] * u_{t-k}``: a depthwise causal convolution over
    ``u`` ``[b, s, channels]`` with ``taps`` ``[kernel, channels]`` (multiplied
    in ``u``'s dtype).  In a packed row a tap that would reach into another
    document or into padding (a different ``segment_id``, or 0) contributes
    0: the segment rule of every convolution in this file."""
    length = u.shape[1]
    conv = u * taps[0].astype(u.dtype)
    for k in range(1, taps.shape[0]):
        shifted = jnp.pad(u, ((0, 0), (k, 0), (0, 0)))[:, :length]
        if segment_ids is not None:
            before = jnp.pad(segment_ids, ((0, 0), (k, 0)))[:, :length]
            same = (segment_ids == before) & (segment_ids != 0)
            shifted = jnp.where(same[:, :, None], shifted, 0)
        conv = conv + shifted * taps[k].astype(u.dtype)
    return conv


class ShortConv(nn.Module):
    """Gated short convolution, LFM2's second kind of token mixer:
    ``[B, C, X] = split3(W_in x)``; ``u = B * X``; ``c_t = sum_k w_k *
    u_{t-k}`` (depthwise, causal, one ``kernel``-tap filter a channel, no
    bias); ``y = W_out (C * c)``.  In a packed row a tap that would reach
    into another document or into padding (a different ``segment_id``, or
    0) contributes 0."""
    kernel: int = 3
    dtype: Any = jnp.bfloat16

    @nn.compact
    def __call__(self, x, segment_ids=None):
        d_model = x.shape[-1]
        bcx = nn.Dense(3 * d_model, use_bias=False, dtype=self.dtype,
                       name='in_proj')(x)
        gate_b, gate_c, value = jnp.split(bcx, 3, axis=-1)
        taps = self.param('conv', nn.initializers.normal(
            1.0 / self.kernel ** 0.5), (self.kernel, d_model))
        with jax.named_scope('pt/lfm2_conv'):
            conv = causal_taps(gate_b * value, taps, segment_ids)
            gated = gate_c * conv
        return nn.Dense(d_model, use_bias=False, dtype=self.dtype,
                        name='out_proj')(gated)


#: added to the sum of squares under the root of :class:`DeltaAttention`'s L2
#: normalisation of q and k (the reference implementation's)
L2_EPS = 1e-6


class DeltaAttention(nn.Module):
    """Kimi Delta Attention (KDA), the linear-attention mixer of Kimi-Linear:
    ``q, k, v = silu(conv(h W_q)), silu(conv(h W_k)), silu(conv(h W_v))`` of
    ``heads x head_dim`` each (``conv``: :func:`causal_taps`, depthwise,
    ``conv_kernel`` taps, no bias); q and k L2-normalised over a head, q
    scaled by ``head_dim ** -0.5``; a log-decay for every key channel ``g =
    -exp(A_log)[head] * softplus((h W_fa) W_fb + dt_bias)`` and a write
    strength ``beta = sigmoid(h W_b)`` a head, both float32; the gated delta
    rule ``ops.kda.kda_chunked`` (its ``head_dim x head_dim`` state restarts
    at every document of a packed row; Pallas kernels where ``head_dim`` is a
    multiple of 128, else the same chunk function under a scan); ``y = RMSNorm(o) * sigmoid((h W_ga)
    W_gb + b_g)`` over a head (learned scale ``o_norm``; ``b_g`` is the
    layer's one bias) and the output
    ``concat(y) W_o``.  A recurrent state kept between calls (``decode``) is
    serving, which is not built."""
    num_heads: int
    head_dim: int = 128
    conv_kernel: int = 4
    gate_rank: int = 128            # of the decay's and the output gate's pair
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    decode: bool = False
    chunks_per_step: int = 2        # between two states kept: ``ops.kda.kda_chunked``

    @nn.compact
    def __call__(self, x, segment_ids=None):
        if self.decode:
            raise NotImplementedError(
                'the delta-rule mixer has no decode path: a recurrent state '
                'kept between calls is serving, which this model does not build')
        from petastorm_tpu.ops.kda import kda_chunked
        heads, hd = self.num_heads, self.head_dim
        width = heads * hd
        f32, highest = jnp.float32, jax.lax.Precision.HIGHEST

        def dense(features, name):
            return nn.Dense(features, use_bias=False, dtype=self.dtype, name=name)
        with jax.named_scope('pt/kda_project'):
            projected = [dense(width, name)(x)
                         for name in ('q_proj', 'k_proj', 'v_proj')]
            # decays and write strengths in float32 at full precision: they
            # are exponents, and a head forgets at the rate they say
            x32 = x.astype(f32)
            rank = nn.Dense(self.gate_rank, use_bias=False, dtype=f32,
                            precision=highest, name='f_a')(x32)
            step = nn.Dense(width, use_bias=False, dtype=f32, precision=highest,
                            name='f_b')(rank)
            dt_bias = self.param('dt_bias', nn.initializers.zeros, (width,))
            a_log = self.param('A_log', nn.initializers.zeros, (heads,))
            g = -jnp.exp(a_log.astype(f32))[:, None] * jax.nn.softplus(
                step + dt_bias).reshape(x.shape[:2] + (heads, hd))
            beta = jax.nn.sigmoid(nn.Dense(
                heads, use_bias=False, dtype=f32, precision=highest,
                name='b_proj')(x32))
            gate = nn.Dense(width, use_bias=True, dtype=self.dtype, name='g_b')(
                dense(self.gate_rank, 'g_a')(x))
        taps = [self.param(name, nn.initializers.normal(
            1.0 / self.conv_kernel ** 0.5), (self.conv_kernel, width))
            for name in ('q_conv', 'k_conv', 'v_conv')]

        # the elementwise parts keep their inputs alone for the backward
        # pass and are recomputed there: their float32 temporaries (five of
        # [tokens, heads * head_dim]) would stand beside the whole layer's
        @jax.checkpoint
        def convolved(projected, taps):
            with jax.named_scope('pt/kda_conv'):
                q, k, v = (nn.silu(causal_taps(u, t, segment_ids)).reshape(
                    x.shape[:2] + (heads, hd)) for u, t in zip(projected, taps))

                def unit(y):
                    y = y.astype(f32)
                    return y * jax.lax.rsqrt(
                        jnp.sum(jnp.square(y), -1, keepdims=True) + L2_EPS)
                # normalised in float32, handed on in the compute dtype as v is
                return ((unit(q) * hd ** -0.5).astype(self.dtype),
                        unit(k).astype(self.dtype), v)

        @jax.checkpoint
        def gated(o, gate, scale):
            with jax.named_scope('pt/kda_project'):
                o = o.astype(f32)
                var = jnp.mean(jnp.square(o), axis=-1, keepdims=True)
                y = o * jax.lax.rsqrt(var + self.norm_eps) * scale
                return (y * jax.nn.sigmoid(gate.astype(f32).reshape(o.shape))) \
                    .astype(self.dtype).reshape(x.shape[:2] + (width,))
        q, k, v = convolved(projected, taps)
        with jax.named_scope('pt/kda_scan'):
            o = kda_chunked(q, k, v, g, beta, segment_ids, dtype=self.dtype,
                            chunks_per_step=self.chunks_per_step)
        o_scale = self.param('o_norm', nn.initializers.ones, (hd,))
        with jax.named_scope('pt/kda_project'):
            return dense(x.shape[-1], 'o_proj')(gated(o, gate, o_scale))


class LatentAttention(nn.Module):
    """Multi-head latent attention (MLA) without a query latent and without
    rotation (Kimi-Linear's ``mla_use_nope``): ``q = h W_q`` of ``heads x
    (qk_nope_dim + qk_rope_dim)``; ``(c, k_b) = h W_kva`` of ``kv_rank +
    qk_rope_dim``; ``(k_a, v) = RMSNorm(c) W_kvb`` of ``heads x (qk_nope_dim +
    v_dim)``; the ONE ``k_b`` a token is shared by all heads; scores ``(q_a .
    k_a + q_b . k_b) * (qk_nope_dim + qk_rope_dim) ** -0.5``; the output
    ``concat(o) W_o`` from heads of ``v_dim``.  ``attn_fn`` gets q and k at
    ``qk_nope_dim + qk_rope_dim`` and v at ``v_dim``, and the ``segment_ids``
    of a packed row.  A latent cache (``decode``) is serving, which is not
    built; a rotated ``k_b`` is not built either."""
    num_heads: int
    kv_rank: int
    qk_nope_dim: int
    qk_rope_dim: int
    v_dim: int
    norm_eps: float = 1e-6
    dtype: Any = jnp.bfloat16
    attn_fn: Callable = flash_attention
    decode: bool = False

    @nn.compact
    def __call__(self, x, segment_ids=None):
        if self.decode:
            raise NotImplementedError(
                'latent attention has no decode path: a latent cache is '
                'serving, which this model does not build')
        heads, nope, shared = self.num_heads, self.qk_nope_dim, self.qk_rope_dim
        with jax.named_scope('pt/mla_project'):
            q = nn.DenseGeneral((heads, nope + shared), axis=-1, use_bias=False,
                                dtype=self.dtype, name='q')(x)
            kv_a = nn.Dense(self.kv_rank + shared, use_bias=False,
                            dtype=self.dtype, name='kv_a')(x)
            latent = RMSNorm(self.norm_eps, name='kv_norm')(
                kv_a[..., :self.kv_rank]).astype(self.dtype)
            kv = nn.DenseGeneral((heads, nope + self.v_dim), axis=-1,
                                 use_bias=False, dtype=self.dtype,
                                 name='kv_b')(latent)
            k_b = kv_a[:, :, None, self.kv_rank:]
            k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(
                k_b, k_b.shape[:2] + (heads, shared))], axis=-1)
            v = kv[..., nope:]
        packed = {} if segment_ids is None else {'segment_ids': segment_ids}
        with jax.named_scope('pt/attention'):
            out = self.attn_fn(q, k, v, causal=True, **packed)
        with jax.named_scope('pt/mla_project'):
            return nn.DenseGeneral(x.shape[-1], axis=(-2, -1), use_bias=False,
                                   dtype=self.dtype, name='out')(out)


class MoEShare(nn.Module):
    """``models.moe.moe_share_apply`` as a module: the router over all
    ``num_experts``, the SwiGLU experts ``experts_held`` here, ``top_k`` a
    token, no capacity.  The selection bias is a buffer (collection
    ``buffers``, no gradient); what was routed where
    (``tokens_per_expert``, ``held_share``, ``over_budget``) is sown into
    the collection ``diagnostics``.  ``d_shared`` > 0 adds the shared expert:
    ONE dense SwiGLU of that width beside the routed share, which every chip
    of an expert-parallel job computes alike and which counts once when the
    shares are added."""
    num_experts: int
    top_k: int
    d_expert: int
    experts_held: Any = None        # global ids held here; None = all
    scale: float = 1.0
    dtype: Any = jnp.bfloat16
    eps: float = 1e-6               # in the normaliser of the selected scores
    d_shared: int = 0
    budget_factor: int = 2          # ``models.moe.share_budget``

    @nn.compact
    def __call__(self, x):
        from petastorm_tpu.models.moe import (
            fan_in_normal, moe_share_apply, moe_share_shapes)
        held = tuple(range(self.num_experts)) if self.experts_held is None \
            else tuple(self.experts_held)
        d_model = x.shape[-1]
        params = {name: self.param(name, fan_in_normal, shape)
                  for name, shape in moe_share_shapes(
                      d_model, self.d_expert, self.num_experts, held).items()}
        bias = self.variable('buffers', 'expert_bias', jnp.zeros,
                             (self.num_experts,), jnp.float32)
        y, stats = moe_share_apply(
            params, x.reshape(-1, d_model), held, self.top_k,
            expert_bias=bias.value, scale=self.scale, eps=self.eps,
            dtype=self.dtype, budget_factor=self.budget_factor)
        for name, value in stats.items():
            self.sow('diagnostics', name, value)
        y = y.reshape(x.shape)
        if self.d_shared:
            with jax.named_scope('pt/moe_shared'):
                def dense(width, name):
                    return nn.Dense(width, use_bias=False, dtype=self.dtype,
                                    name=name)
                y = y + dense(d_model, 'shared_w2')(
                    nn.silu(dense(self.d_shared, 'shared_w1')(x))
                    * dense(self.d_shared, 'shared_w3')(x))
        return y


class Block(nn.Module):
    num_heads: int
    d_ff: int
    dtype: Any = jnp.bfloat16
    attn_fn: Callable = flash_attention
    causal: bool = True
    decode: bool = False
    max_decode_len: int = 2048
    num_kv_heads: Any = None
    pos_mode: Any = None
    #: Token mixer: 'attention', 'conv' (:class:`ShortConv`), 'kda'
    #: (:class:`DeltaAttention`, built from the ``kda`` dict of its sizes) or
    #: 'mla' (:class:`LatentAttention`, from the ``mla`` dict).
    mixer: str = 'attention'
    kda: Any = None
    mla: Any = None
    #: Feed-forward: 'gelu' (two matrices with biases), 'swiglu'
    #: (``W2(silu(W1 h) * W3 h)``, no bias) or 'moe' (:class:`MoEShare`,
    #: built from the ``moe`` dict of its fields).
    ffn: str = 'gelu'
    moe: Any = None
    rope_base: float = 10000.0
    qk_norm: bool = False
    norm_eps: float = 1e-6
    use_bias: bool = True
    conv_kernel: int = 3

    @nn.compact
    def __call__(self, x, positions=None, segment_ids=None):
        h = RMSNorm(self.norm_eps, name='ln1')(x)
        if self.mixer == 'attention':
            with jax.named_scope('pt/attention'):
                x = x + Attention(
                    self.num_heads, self.dtype, self.attn_fn,
                    causal=self.causal, decode=self.decode,
                    max_decode_len=self.max_decode_len,
                    num_kv_heads=self.num_kv_heads, pos_mode=self.pos_mode,
                    rope_base=self.rope_base, qk_norm=self.qk_norm,
                    norm_eps=self.norm_eps, use_bias=self.use_bias,
                    name='attn')(h, positions, segment_ids)
        elif self.mixer == 'conv':
            x = x + ShortConv(self.conv_kernel, self.dtype,
                              name='conv')(h, segment_ids)
        elif self.mixer == 'kda':
            x = x + DeltaAttention(
                dtype=self.dtype, decode=self.decode, norm_eps=self.norm_eps,
                name='kda', **self.kda)(h, segment_ids)
        elif self.mixer == 'mla':
            x = x + LatentAttention(
                dtype=self.dtype, attn_fn=self.attn_fn, decode=self.decode,
                norm_eps=self.norm_eps, name='attn', **self.mla)(h, segment_ids)
        else:
            raise ValueError("mixer must be 'attention', 'conv', 'kda' or "
                             "'mla', got %r" % (self.mixer,))
        h = RMSNorm(self.norm_eps, name='ln2')(x)
        if self.ffn == 'gelu':
            h = nn.Dense(self.d_ff, dtype=self.dtype, name='ffw_in')(h)
            return x + nn.Dense(x.shape[-1], dtype=self.dtype,
                                name='ffw_out')(nn.gelu(h))
        if self.ffn == 'swiglu':
            gate = nn.Dense(self.d_ff, use_bias=False, dtype=self.dtype,
                            name='w1')(h)
            up = nn.Dense(self.d_ff, use_bias=False, dtype=self.dtype,
                          name='w3')(h)
            return x + nn.Dense(x.shape[-1], use_bias=False, dtype=self.dtype,
                                name='w2')(nn.silu(gate) * up)
        if self.ffn == 'moe':
            return x + MoEShare(dtype=self.dtype, name='moe', **self.moe)(h)
        raise ValueError("ffn must be 'gelu', 'swiglu' or 'moe', got %r"
                         % (self.ffn,))


class TransformerLM(nn.Module):
    """Decoder-only LM: tokens [batch, seq] -> logits [batch, seq, vocab]."""

    vocab_size: int
    d_model: int = 512
    num_heads: int = 8
    num_layers: int = 6
    d_ff: int = 2048
    max_seq_len: int = 2048
    dtype: Any = jnp.bfloat16
    attn_fn: Callable = flash_attention
    remat: bool = False  # jax.checkpoint each block: FLOPs for HBM
    decode: bool = False  # KV-cache incremental mode (models.decoding)
    num_kv_heads: Any = None  # GQA: KV heads < query heads (see Attention)
    #: 'learned' table | 'rope' rotary q/k | 'none' (no positions at all: the
    #: mixers 'kda' and 'mla' take none)
    pos_embed: str = 'learned'
    #: The mixer of each layer, 'full_attention' or 'conv' (the names of
    #: LFM2's ``layer_types``), 'kda' or 'mla' (built from the dicts ``kda``
    #: and ``mla`` of :class:`DeltaAttention`'s and :class:`LatentAttention`'s
    #: sizes), ``num_layers`` long; None = attention everywhere.
    layer_types: Any = None
    kda: Any = None
    mla: Any = None
    #: False: an output head of its own (``lm_head``, no bias) in place of
    #: the embedding's transpose.
    tie_embedding: bool = True
    #: The feed-forward of the layers: 'gelu', 'swiglu', or 'moe' with the
    #: first ``num_dense_layers`` layers 'swiglu' of width ``d_ff`` and the
    #: others :class:`MoEShare` built from ``moe``.
    ffn: str = 'gelu'
    num_dense_layers: int = 0
    moe: Any = None
    rope_base: float = 10000.0
    qk_norm: bool = False
    norm_eps: float = 1e-6
    use_bias: bool = True
    conv_kernel: int = 3

    @nn.compact
    def __call__(self, tokens, positions=None, segment_ids=None):
        """``positions`` overrides the default row-absolute ``arange``
        positions — pass ``packing.pack_*``'s per-segment ``positions`` so
        each packed document is embedded (or RoPE-rotated) as if it
        started at 0.  ``segment_ids`` keeps every kind of mixer inside each
        packed document."""
        if self.pos_embed not in ('learned', 'rope', 'none'):
            raise ValueError("pos_embed must be 'learned', 'rope' or 'none', "
                             "got %r" % (self.pos_embed,))
        embed = nn.Embed(self.vocab_size, self.d_model, name='embed',
                         dtype=self.dtype)
        x = embed(tokens)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(tokens.shape[1]),
                                         tokens.shape)
        if self.pos_embed == 'learned':
            pos = nn.Embed(self.max_seq_len, self.d_model, name='pos_embed',
                           dtype=self.dtype)(positions)
            x = x + pos
        block = Block
        if self.remat:
            block = nn.remat(Block)
        rope_mode = 'rope' if self.pos_embed == 'rope' else None
        layer_types = self.layer_types or ('full_attention',) * self.num_layers
        if len(layer_types) != self.num_layers:
            raise ValueError('layer_types names %d layers, num_layers is %d'
                             % (len(layer_types), self.num_layers))
        for i, kind in enumerate(layer_types):
            ffn = self.ffn
            if ffn == 'moe' and i < self.num_dense_layers:
                ffn = 'swiglu'
            x = block(self.num_heads, self.d_ff, self.dtype, self.attn_fn,
                      decode=self.decode, max_decode_len=self.max_seq_len,
                      num_kv_heads=self.num_kv_heads, pos_mode=rope_mode,
                      mixer='attention' if kind == 'full_attention' else kind,
                      ffn=ffn, moe=self.moe, kda=self.kda, mla=self.mla,
                      rope_base=self.rope_base,
                      qk_norm=self.qk_norm, norm_eps=self.norm_eps,
                      use_bias=self.use_bias, conv_kernel=self.conv_kernel,
                      name='block_%d' % i)(x, positions, segment_ids)
        x = RMSNorm(self.norm_eps, name='ln_f')(x)
        with jax.named_scope('pt/lm_head_loss'):
            if self.tie_embedding:
                # attend() reuses the (vocab-sharded) embedding
                return embed.attend(x.astype(self.dtype)).astype(jnp.float32)
            return nn.Dense(self.vocab_size, use_bias=False, dtype=self.dtype,
                            name='lm_head')(x).astype(jnp.float32)


# ---------------------------------------------------------------------------
# sharding rules
# ---------------------------------------------------------------------------

#: (path-suffix match, PartitionSpec factory) — Megatron TP sandwich.
def _spec_for(path, model_axis):
    names = [p.key for p in path if hasattr(p, 'key')]
    leaf = names[-1] if names else ''
    parent = names[-2] if len(names) > 1 else ''
    if parent in ('embed', 'pos_embed'):
        return P(model_axis, None)             # vocab/position sharded
    if parent == 'qkv':
        # kernel [d_model, 3, heads, head_dim] — shard heads.
        return P(None, None, model_axis, None) if leaf == 'kernel' \
            else P(None, model_axis, None)     # bias [3, heads, head_dim]
    if parent == 'q':
        # GQA query proj: kernel [d_model, heads, head_dim] — shard heads.
        return P(None, model_axis, None) if leaf == 'kernel' \
            else P(model_axis, None)
    if parent == 'kv':
        # GQA kv proj: kernel [d_model, 2, kv_heads, head_dim].  The model
        # axis size must divide kv_heads; param_shardings falls back to
        # replication per leaf when it doesn't (e.g. MQA with kv_heads=1).
        return P(None, None, model_axis, None) if leaf == 'kernel' \
            else P(None, model_axis, None)
    if parent == 'out':
        # kernel [heads, head_dim, d_model] — shard input heads.
        return P(model_axis, None, None) if leaf == 'kernel' else P(None)
    if parent == 'ffw_in':
        return P(None, model_axis) if leaf == 'kernel' else P(model_axis)
    if parent == 'ffw_out':
        return P(model_axis, None) if leaf == 'kernel' else P(None)
    if parent == 'kv_b':
        # latent -> heads: kernel [kv_rank, heads, nope + v_dim], shard heads
        return P(None, model_axis, None)
    if parent in _COLUMN_PARENTS:
        # [d_model, width | heads * head_dim | vocab]: shard the outputs
        return P(None, model_axis) if leaf == 'kernel' else P(model_axis)
    if parent in _ROW_PARENTS:
        return P(model_axis, None)             # [width, d_model]: the inputs
    if parent == 'kda' and leaf in _KDA_CHANNEL_LEAVES:
        # one entry a channel (heads * head_dim, heads for A_log), the last axis
        return P(model_axis) if leaf in ('dt_bias', 'A_log') \
            else P(None, model_axis)
    return P()                                 # norms & everything else: replicated


#: The delta-rule mixer's projections to ``heads * head_dim`` channels (and
#: ``b_proj`` to heads), the shared expert's two inputs and the untied head
#: shard their outputs; ``o_proj`` and the shared expert's ``shared_w2``
#: their inputs: the same sandwich, heads kept whole.
_COLUMN_PARENTS = frozenset(['q_proj', 'k_proj', 'v_proj', 'f_b', 'g_b', 'b_proj',
                             'shared_w1', 'shared_w3', 'lm_head'])
_ROW_PARENTS = frozenset(['o_proj', 'shared_w2'])
_KDA_CHANNEL_LEAVES = frozenset(['q_conv', 'k_conv', 'v_conv', 'dt_bias', 'A_log'])
#: Replicated on purpose, by the parent's name: the latent projection
#: ``kv_a`` (one latent a token, shared by all heads), the low-rank inputs
#: ``f_a`` and ``g_a`` (their 128 outputs feed every head), every norm.
REPLICATED_PARENTS = frozenset(['kv_a', 'f_a', 'g_a', 'kv_norm', 'ln1', 'ln2',
                                'ln_f', 'q_norm', 'k_norm'])
#: ... and by the leaf's: the delta-rule mixer's norm over a head's channels
REPLICATED_LEAVES = frozenset(['o_norm'])


def megatron_spec_fn(model_axis='model'):
    """Public path→PartitionSpec callable with the Megatron TP rules — the
    ``base_spec_fn`` hook for :func:`petastorm_tpu.parallel.fsdp_shardings`
    (FSDP × TP composition)."""
    return functools.partial(_spec_for, model_axis=model_axis)


def param_shardings(params, mesh, model_axis='model'):
    """NamedSharding pytree for ``TransformerLM`` params over ``mesh``.

    Tensor parallelism the XLA way: annotate the parameters, let GSPMD
    propagate through the matmuls and insert the block all-reduces —
    never hand-written collectives (scaling-book recipe).
    """
    if model_axis not in mesh.axis_names:
        return jax.tree_util.tree_map(lambda _: NamedSharding(mesh, P()), params)
    axis_size = mesh.shape[model_axis]

    def leaf_sharding(path, leaf):
        spec = _spec_for(path, model_axis)
        # A dim the rule would shard must be divisible by the axis size;
        # otherwise fall back to replication for this leaf (e.g. MQA
        # kv_heads=1 under 2-way TP, or an odd vocab).
        for dim, axis in zip(leaf.shape, spec):
            if axis == model_axis and dim % axis_size:
                return NamedSharding(mesh, P())
        return NamedSharding(mesh, spec)

    return jax.tree_util.tree_map_with_path(leaf_sharding, params)


def make_attn_fn(mesh=None, strategy='flash', seq_axis='seq',
                 batch_axis='data', head_axis='model', block_k=None,
                 segment_ids=None, causal=True):
    """Attention implementation for a (mesh, strategy) pair.

    'flash'   — Pallas kernel, no sequence sharding (or inside Ulysses).
    'ring'    — K/V rotate the ICI ring over ``seq_axis`` (longest contexts);
                ``block_k`` additionally chunks each hop's score tile (set
                it when seq_local² would not fit — see
                ``parallel.ring_attention``).
    'ulysses' — all-to-all seq<->head reshard, flash locally.
    'dense'   — O(seq²) oracle (tests only).

    ``segment_ids`` ([batch, seq], 0 = padding — see
    ``petastorm_tpu.jax.packing``) restricts attention to packed-row
    segments under every strategy; for 'ring'/'ulysses' place them with
    the sequence sharding (``P(batch_axis, seq_axis)``).
    """
    from petastorm_tpu.parallel import (full_attention, make_ring_attention,
                                        make_ulysses_attention)
    packed = segment_ids is not None
    if strategy == 'flash':
        return (functools.partial(flash_attention, segment_ids=segment_ids)
                if packed else flash_attention)
    if strategy == 'dense':
        return (functools.partial(full_attention, segment_ids=segment_ids)
                if packed else full_attention)
    if mesh is None:
        raise ValueError('strategy %r needs a mesh' % (strategy,))
    if strategy == 'ring':
        fn, _ = make_ring_attention(mesh, seq_axis=seq_axis, batch_axis=batch_axis,
                                    head_axis=head_axis, causal=causal,
                                    block_k=block_k, packed=packed)
    elif strategy == 'ulysses':
        fn, _ = make_ulysses_attention(
            mesh, seq_axis=seq_axis, batch_axis=batch_axis, head_axis=head_axis,
            causal=causal, attn_fn=flash_attention, packed=packed)
    else:
        raise ValueError('unknown attention strategy %r' % (strategy,))
    return functools.partial(_check_curried_causal, fn, segment_ids, causal)


def _check_curried_causal(fn, segment_ids, curried_causal, q, k, v,
                          causal=True):
    # shard_map-wrapped fns curried causal at construction time; a caller
    # asking for different masking (e.g. an encoder calling a causal-curried
    # wrapper) must hear about it, not silently get the curried behavior.
    if causal != curried_causal:
        raise ValueError(
            'attn_fn was built with causal=%s but called with causal=%s — '
            'pass causal=%s to make_attn_fn' % (curried_causal, causal, causal))
    if segment_ids is not None:
        return fn(q, k, v, segment_ids)
    return fn(q, k, v)
