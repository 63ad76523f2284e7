"""``kimi-linear-48b-a3b``: everything that belongs to this configuration alone.

The harness loads this file by the configuration's name and talks to
:class:`Config` only.  The chip holds one chip's share of an expert-parallel
job in which 32 chips share each layer: 8 of the 256 routed experts of every
expert layer, the shared expert, the mixers, the dense feed-forward and the
router whole, 1/8 of the vocabulary (of the embedding and of the untied
head), and published layers 1-5 (``layers_here``): KDA, KDA, KDA, MLA, KDA.
Every width is as published.

* The system under test: the path of ``lfm2-24b-a2b`` (documents in Unischema
  Parquet, ``make_reader`` -> ``PackedDataLoader`` -> the jitted AdamW step
  around ``TransformerLM``) with the mixers ``'kda'`` and ``'mla'``, the
  shared expert and the untied head.  The dataset, the loader's arguments,
  the delivered-batch check, the step and the AdamW update written out are
  that configuration's own code, inherited through ``catalog._module``:
  nothing of the data path is new here.
* The yardstick: the plain float32 reference of the same steps, below.  It
  imports nothing of ``petastorm_tpu``, is handed the same DOCUMENTS, packs
  them its own way and is compared one document at a time.  Its delta rule
  runs token by token.
"""

import os
import types

import numpy as np

import catalog
import oracle

packed = catalog._module(os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                      'lfm2-24b-a2b.py'))

#: the reference's planted faults that ride into the jitted row as flags
ROW_FAULTS = ('leaking_tap', 'state_leak', 'scalar_decay')
KDA_CHUNK = 64


def kda_flops_per_token_head(head_dim, chunk=KDA_CHUNK):
    """FLOPs of one token and head through the chunked delta rule, forward:
    five ``chunk``-deep products of ``head_dim`` wide (``A``, the solve
    applied to keys and to values, the two intra-chunk products of the
    output) and three products with the state (read-out, correction,
    update)."""
    return 5 * 2 * head_dim * chunk + 3 * 2 * head_dim * head_dim


def parameter_shapes(c):
    """{path: (shape, initialiser)} of every parameter held here, named as
    flax names the modules of ``TransformerLM``.  Initialisers as
    ``lfm2-24b-a2b.py`` has them, and ``'zeros'``, ``'a_log'`` (log of
    uniform(1, 16)) and ``'dt_bias'`` (inverse softplus of a log-uniform step
    in [0.001, 0.1])."""
    d, heads = c.hidden, c.heads
    held = len(c.experts_held)
    width = c.kda_heads * c.kda_head_dim
    shapes = {('embed', 'embedding'): ((c.vocab, d), ('normal', 0.02)),
              ('lm_head', 'kernel'): ((d, c.vocab), 'fan_in'),
              ('ln_f', 'scale'): ((d,), 'ones')}
    for i, (kind, dense) in enumerate(c.layers):
        block = 'block_%d' % i
        shapes[block, 'ln1', 'scale'] = ((d,), 'ones')
        shapes[block, 'ln2', 'scale'] = ((d,), 'ones')
        if kind == 'kda':
            for name in ('q', 'k', 'v'):
                shapes[block, 'kda', name + '_proj', 'kernel'] = ((d, width), 'fan_in')
                shapes[block, 'kda', name + '_conv'] = (
                    (c.conv_kernel, width), ('normal', 1.0 / np.sqrt(c.conv_kernel)))
            shapes[block, 'kda', 'f_a', 'kernel'] = ((d, c.gate_rank), 'fan_in')
            shapes[block, 'kda', 'f_b', 'kernel'] = ((c.gate_rank, width), 'fan_in')
            shapes[block, 'kda', 'dt_bias'] = ((width,), 'dt_bias')
            shapes[block, 'kda', 'A_log'] = ((c.kda_heads,), 'a_log')
            shapes[block, 'kda', 'b_proj', 'kernel'] = ((d, c.kda_heads), 'fan_in')
            shapes[block, 'kda', 'g_a', 'kernel'] = ((d, c.gate_rank), 'fan_in')
            shapes[block, 'kda', 'g_b', 'kernel'] = ((c.gate_rank, width), 'fan_in')
            shapes[block, 'kda', 'g_b', 'bias'] = ((width,), 'zeros')
            shapes[block, 'kda', 'o_norm'] = ((c.kda_head_dim,), 'ones')
            shapes[block, 'kda', 'o_proj', 'kernel'] = ((width, d), 'fan_in')
        else:
            shapes[block, 'attn', 'q', 'kernel'] = (
                (d, heads, c.nope + c.shared_key), 'fan_in')
            shapes[block, 'attn', 'kv_a', 'kernel'] = (
                (d, c.kv_rank + c.shared_key), 'fan_in')
            shapes[block, 'attn', 'kv_norm', 'scale'] = ((c.kv_rank,), 'ones')
            shapes[block, 'attn', 'kv_b', 'kernel'] = (
                (c.kv_rank, heads, c.nope + c.v_dim), 'fan_in')
            shapes[block, 'attn', 'out', 'kernel'] = ((heads, c.v_dim, d), 'fan_out_in')
        if dense:
            shapes[block, 'w1', 'kernel'] = ((d, c.d_ff), 'fan_in')
            shapes[block, 'w3', 'kernel'] = ((d, c.d_ff), 'fan_in')
            shapes[block, 'w2', 'kernel'] = ((c.d_ff, d), 'fan_in')
        else:
            shapes[block, 'moe', 'router'] = ((d, c.num_experts), 'fan_in')
            shapes[block, 'moe', 'w1'] = ((held, d, c.d_expert), 'expert')
            shapes[block, 'moe', 'w3'] = ((held, d, c.d_expert), 'expert')
            shapes[block, 'moe', 'w2'] = ((held, c.d_expert, d), 'expert')
            shapes[block, 'moe', 'shared_w1', 'kernel'] = ((d, c.d_shared), 'fan_in')
            shapes[block, 'moe', 'shared_w3', 'kernel'] = ((d, c.d_shared), 'fan_in')
            shapes[block, 'moe', 'shared_w2', 'kernel'] = ((c.d_shared, d), 'fan_in')
    return shapes


class Config(packed.Config):
    def __init__(self, spec, tiny=False, **sizes_for_a_reading):
        self.spec = spec
        linear = spec['linear_attn_config']
        sizes = {k: v for k, v in spec.items() if not isinstance(v, (dict, list))}
        sizes.update(spec['dataset'], experts_held=spec['experts_held'],
                     published_num_experts=spec['published']['num_experts'],
                     kda_num_heads=linear['num_heads'],
                     kda_head_dim=linear['head_dim'])
        if tiny:
            sizes.update(spec['tiny'])
        sizes.update(sizes_for_a_reading)
        self.hidden = sizes['hidden_size']
        self.heads = sizes['num_attention_heads']
        self.nope = sizes['qk_nope_head_dim']
        self.shared_key = sizes['qk_rope_head_dim']   # unrotated: mla_use_nope
        self.v_dim = sizes['v_head_dim']
        self.kv_rank = sizes['kv_lora_rank']
        self.kda_heads = sizes['kda_num_heads']
        self.kda_head_dim = sizes['kda_head_dim']
        self.gate_rank = self.kda_head_dim            # assumed.gate_rank
        self.conv_kernel = linear['short_conv_kernel_size']
        self.kda_chunks_per_step = sizes['kda_chunks_per_step']
        self.d_ff = sizes['intermediate_size']
        self.d_expert = sizes['moe_intermediate_size']
        self.d_shared = sizes['num_shared_experts'] * self.d_expert
        self.num_experts = sizes['published_num_experts']    # the router's width
        self.experts_held = tuple(sizes['experts_held'])
        self.top_k = sizes['num_experts_per_token']
        self.route_scale = float(sizes['routed_scaling_factor'])
        self.route_eps = 1e-20                        # assumed.topk_weight_eps
        self.budget_factor = sizes['moe_budget_factor']
        self.vocab = sizes['vocab_size']
        self.norm_eps = sizes['rms_norm_eps']
        assert spec['mla_use_nope'] and spec['q_lora_rank'] is None
        assert spec['moe_renormalize'] and spec['num_expert_group'] == 1
        assert spec['moe_router_activation_func'] == 'sigmoid'
        assert not spec['tie_word_embeddings'] and spec['kda_chunk'] == KDA_CHUNK
        #: losses of a document's first tokens that ``sample_loss`` holds: those
        #: a tap reaching back over the document's start would touch
        self.head_tokens = self.conv_kernel - 1
        #: (mixer, has the dense feed-forward) of each published layer held here
        self.layers = [('mla' if i in linear['full_attn_layers'] else 'kda',
                        i <= spec['first_k_dense_replace'])
                       for i in spec['layers_here']]
        assert all(i in linear['kda_layers'] + linear['full_attn_layers']
                   for i in spec['layers_here'])
        assert len(self.layers) == spec['num_hidden_layers']
        assert len(spec['experts_held']) == spec['num_experts']
        self.batch = sizes['batch']                  # packed rows a step
        self.max_len = sizes['max_len']
        self.rows = sizes['documents']               # stored rows: documents
        self.rows_per_rowgroup = sizes['rows_per_rowgroup']
        self.length_law = (sizes['length_median'], sizes['length_sigma'],
                           sizes['length_min'], self.max_len)
        self.layout_seed = sizes['layout_seed']
        self.zipf = sizes['token_zipf_exponent']
        self.compute_dtype = sizes['compute_dtype']
        self.optimizer = spec['optimizer']
        self.donate_state = True
        self._jits = {}

    # -- weights, from the seed ------------------------------------------------

    def init_params(self, key):
        import jax
        import jax.numpy as jnp
        params = {}
        for index, (path, (shape, kind)) in enumerate(
                sorted(parameter_shapes(self).items())):
            leaf_key = jax.random.fold_in(key, index)
            if kind in ('ones', 'zeros'):
                leaf = jnp.full(shape, kind == 'ones', jnp.float32)
            elif kind == 'a_log':
                leaf = jnp.log(jax.random.uniform(leaf_key, shape, jnp.float32, 1.0, 16.0))
            elif kind == 'dt_bias':
                step = jnp.exp(jax.random.uniform(
                    leaf_key, shape, jnp.float32, np.log(0.001), np.log(0.1)))
                leaf = step + jnp.log(-jnp.expm1(-step))     # softplus(leaf) = step
            else:
                std = kind[1] if isinstance(kind, tuple) \
                    else 1.0 / np.sqrt(packed.fan_in_of(shape, kind))
                leaf = np.float32(std) * jax.random.normal(leaf_key, shape, jnp.float32)
            node = params
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = leaf
        return params

    # -- the program's step (``train_step`` is the inherited one) -------------

    def model(self):
        import jax.numpy as jnp
        from petastorm_tpu.models.transformer import TransformerLM
        from petastorm_tpu.ops import flash_attention
        return TransformerLM(
            vocab_size=self.vocab, d_model=self.hidden, num_heads=self.heads,
            num_layers=len(self.layers), d_ff=self.d_ff, max_seq_len=self.max_len,
            dtype=jnp.dtype(self.compute_dtype), attn_fn=flash_attention,
            remat=True, pos_embed='none',
            layer_types=tuple(kind for kind, _ in self.layers), ffn='moe',
            num_dense_layers=sum(dense for _, dense in self.layers),
            kda={'num_heads': self.kda_heads, 'head_dim': self.kda_head_dim,
                 'conv_kernel': self.conv_kernel, 'gate_rank': self.gate_rank,
                 'chunks_per_step': self.kda_chunks_per_step},
            mla={'num_heads': self.heads, 'kv_rank': self.kv_rank,
                 'qk_nope_dim': self.nope, 'qk_rope_dim': self.shared_key,
                 'v_dim': self.v_dim},
            moe={'num_experts': self.num_experts, 'top_k': self.top_k,
                 'd_expert': self.d_expert, 'experts_held': self.experts_held,
                 'scale': self.route_scale, 'eps': self.route_eps,
                 'd_shared': self.d_shared, 'budget_factor': self.budget_factor},
            norm_eps=self.norm_eps, use_bias=False, tie_embedding=False)

    # -- yardstick: the plain reference -----------------------------------------

    def reference_parts(self, precision='float32'):
        """Plain ``jax.numpy`` Kimi-Linear share in float32 at ``highest``
        matmul precision, over ONE packed row (``[L]`` tokens, segment ids) so
        that it fits at the published widths; the parts by name, which the
        tests compare one by one.  The delta rule token by token (a scan over
        blocks of 64 tokens, each recomputed in the backward pass, so that 8192
        states of 2 MB are never kept at once); latent attention as a dense
        masked softmax, one head at a time; the convolutions as four shifted
        multiplies; the experts as a loop over the held ids, the shared expert
        beside them.

        ``precision`` as in ``lfm2-24b-a2b.py``: ``'fp8'`` is the control,
        ``'bf16'`` the stated precision's own rounding, of the operands of
        every matrix product (q, k and v of the delta rule among them).  The
        router, the decays, the write strengths and the state stay in float32
        in all three, as in the program (``assumed.float32_parts``).

        ``faults``, a row of three flags (``ROW_FAULTS``): ``leaking_tap``, a
        convolution that was never handed the segment ids; ``state_leak``, a
        state that is not restarted at a document's start; ``scalar_decay``,
        a head's mean log-decay in place of the one a key channel."""
        import jax
        import jax.numpy as jnp
        from jax import lax

        highest = lax.Precision.HIGHEST
        q = oracle.operand_rounding(precision)
        eps, top_k = np.float32(self.norm_eps), self.top_k
        heads, nope, v_dim = self.heads, self.nope, self.v_dim
        kda_heads, hd = self.kda_heads, self.kda_head_dim
        block_tokens = 64

        def mm(a, b):
            return jnp.dot(q(a), q(b), precision=highest)

        def exact(a, b):
            return jnp.dot(a, b, precision=highest)

        def rms(x, scale):
            return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
                * scale

        def shifted(x, k):
            return jnp.pad(x, ((k, 0),) + ((0, 0),) * (x.ndim - 1))[:len(x)]

        def conv(u, taps, seg, leaking):
            out = taps[0] * u
            for k in range(1, self.conv_kernel):
                inside = (seg == shifted(seg, k)) & (seg != 0)
                # the planted fault: every tap reaches whatever lies before it
                inside |= leaking & (jnp.arange(len(seg)) >= k)
                out = out + taps[k] * jnp.where(inside[:, None], shifted(u, k), 0)
            return out

        def delta_rule(qs, ks, vs, g, beta, first):
            """Token by token: ``qs``, ``ks``, ``g`` ``[L, H, d_k]``, ``vs``
            ``[L, H, d_v]``, ``beta`` ``[L, H]``, ``first`` ``[L]`` (the state
            restarts here).  Returns ``[L, H, d_v]``."""
            def token(state, xs):
                q_t, k_t, v_t, g_t, beta_t, first_t = xs
                state = jnp.where(first_t, 0.0, state)
                decayed = jnp.exp(g_t)[:, :, None] * state             # [H, K, V]
                seen = jnp.sum(decayed * k_t[:, :, None], axis=1)      # S'^T k
                state = decayed + (beta_t[:, None] * k_t)[:, :, None] \
                    * (v_t - seen)[:, None, :]
                return state, jnp.sum(state * q_t[:, :, None], axis=1)

            @jax.checkpoint
            def block(state, xs):
                # eight tokens a turn of the loop: the loop's own work a token
                # is as long on the chip as the token's
                return lax.scan(token, state, xs, unroll=8)
            length = len(first)
            pad = -length % block_tokens
            xs = tuple(jnp.pad(x, ((0, pad),) + ((0, 0),) * (x.ndim - 1)).reshape(
                (-1, block_tokens) + x.shape[1:])
                for x in (qs, ks, vs, g, beta, first))
            state = jnp.zeros((qs.shape[1], qs.shape[2], vs.shape[2]), jnp.float32)
            _, out = lax.scan(block, state, xs)
            return out.reshape((-1,) + out.shape[2:])[:length]

        def kda_mixer(p, h, seg, faults):
            leaking, state_leak, scalar_decay = faults[0], faults[1], faults[2]
            real = seg != 0

            def branch(name):
                u = conv(mm(h, p[name + '_proj']['kernel']), p[name + '_conv'],
                         seg, leaking)
                return q(jax.nn.silu(u).reshape(-1, kda_heads, hd))

            def unit(x):
                return x * lax.rsqrt(jnp.sum(jnp.square(x), -1, keepdims=True)
                                     + np.float32(1e-6))
            qs = unit(branch('q')) * np.float32(hd ** -0.5)
            ks, vs = unit(branch('k')), branch('v')
            step = exact(exact(h, p['f_a']['kernel']), p['f_b']['kernel']) \
                + p['dt_bias']
            g = -jnp.exp(p['A_log'])[None, :, None] \
                * jax.nn.softplus(step).reshape(-1, kda_heads, hd)
            # the planted fault: one decay a head, the plain gated delta rule
            g = jnp.where(scalar_decay, jnp.mean(g, -1, keepdims=True), g)
            g = jnp.where(real[:, None, None], g, 0.0)
            beta = jax.nn.sigmoid(exact(h, p['b_proj']['kernel']))
            beta = jnp.where(real[:, None], beta, 0.0)       # padding writes nothing
            first = (seg != shifted(seg, 1)) & ~state_leak
            o = delta_rule(qs, ks, vs, g, beta, first)
            o = jnp.where(real[:, None, None], o, 0.0)
            gate = mm(mm(h, p['g_a']['kernel']), p['g_b']['kernel']) + p['g_b']['bias']
            y = rms(o, p['o_norm']) \
                * jax.nn.sigmoid(gate.reshape(-1, kda_heads, hd))
            return mm(y.reshape(len(seg), -1), p['o_proj']['kernel'])

        def mla_mixer(p, h, seg):
            d = h.shape[-1]
            qs = mm(h, p['q']['kernel'].reshape(d, -1)).reshape(len(seg), heads, -1)
            kv_a = mm(h, p['kv_a']['kernel'])
            latent = rms(kv_a[:, :self.kv_rank], p['kv_norm']['scale'])
            kv = mm(latent, p['kv_b']['kernel'].reshape(self.kv_rank, -1)).reshape(
                len(seg), heads, nope + v_dim)
            k_shared = kv_a[:, self.kv_rank:]        # one a token, for every head
            at = jnp.arange(len(seg))
            mask = (seg[:, None] == seg[None, :]) & (seg[:, None] != 0) \
                & (at[None, :] <= at[:, None])
            scale = np.float32((nope + self.shared_key) ** -0.5)

            @jax.checkpoint
            def one_head(args):
                qh, kh, vh = args
                scores = (mm(qh[:, :nope], kh.T) + mm(qh[:, nope:], k_shared.T)) * scale
                scores = jnp.where(mask, scores, -jnp.inf)
                scores = jnp.where(mask.any(-1, keepdims=True), scores, 0.0)
                weights = jnp.where(mask, jax.nn.softmax(scores, axis=-1), 0.0)
                return mm(weights, vh)
            out = lax.map(one_head, (jnp.moveaxis(qs, 1, 0),
                                     jnp.moveaxis(kv[..., :nope], 1, 0),
                                     jnp.moveaxis(kv[..., nope:], 1, 0)))
            return mm(jnp.moveaxis(out, 0, 1).reshape(len(seg), -1),
                      p['out']['kernel'].reshape(-1, d))

        def swiglu(h, w1, w3, w2):
            return mm(jax.nn.silu(mm(h, w1)) * mm(h, w3), w2)

        def experts(p, bias, h, expert_on, held=self.experts_held):
            """What the experts ``held`` (global ids, in the order of the
            matrices' leading axis) add for the tokens ``h``; ``expert_on``
            has one entry for each of them."""
            scores = jax.nn.sigmoid(exact(h, p['router']))
            _, chosen = lax.top_k(lax.stop_gradient(scores + bias), top_k)
            picked = jnp.take_along_axis(scores, chosen, axis=-1)
            weights = picked / (jnp.sum(picked, -1, keepdims=True)
                                + np.float32(self.route_eps)) \
                * np.float32(self.route_scale)
            out = jnp.zeros_like(h)
            for j, expert in enumerate(held):
                weight = jnp.sum(jnp.where(chosen == expert, weights, 0.0), -1)
                out = out + (weight * expert_on[j])[:, None] * swiglu(
                    h, p['w1'][j], p['w3'][j], p['w2'][j])
            return out

        def shared_expert(p, h):
            return swiglu(h, p['shared_w1']['kernel'], p['shared_w3']['kernel'],
                          p['shared_w2']['kernel'])

        def layer(index, p, bias, x, seg, expert_on, faults):
            kind, dense = self.layers[index]
            h = rms(x, p['ln1']['scale'])
            x = x + (kda_mixer(p['kda'], h, seg, faults) if kind == 'kda'
                     else mla_mixer(p['attn'], h, seg))
            h = rms(x, p['ln2']['scale'])
            if dense:
                return x + swiglu(h, p['w1']['kernel'], p['w3']['kernel'],
                                  p['w2']['kernel'])
            # ``expert_on``: the held experts, then the shared one
            return x + experts(p['moe'], bias, h, expert_on[:-1]) \
                + expert_on[-1] * shared_expert(p['moe'], h)

        def logits(params, buffers, tokens, seg, expert_on, faults):
            x = params['embed']['embedding'][tokens]
            for index in range(len(self.layers)):
                name = 'block_%d' % index
                bias = buffers[name]['moe']['expert_bias'] if name in buffers else None
                # one layer's activations at a time
                x = jax.checkpoint(layer, static_argnums=(0,))(
                    index, params[name], bias, x, seg, expert_on, faults)
            return mm(rms(x, params['ln_f']['scale']), params['lm_head']['kernel'])

        def row_loss(params, buffers, tokens, seg, scale, expert_on, faults):
            """(the row's share of the batch's loss, its weighted token losses)."""
            row_logits = logits(params, buffers, tokens, seg, expert_on, faults)
            targets = jnp.concatenate([tokens[1:], tokens[:1]])
            weights = ((seg == jnp.concatenate([seg[1:], jnp.zeros_like(seg[:1])]))
                       & (seg != 0)).astype(jnp.float32)
            losses = weights * (
                jax.nn.logsumexp(row_logits, axis=-1)
                - jnp.take_along_axis(row_logits, targets[:, None], 1)[:, 0])
            return jnp.sum(losses) * scale, losses
        return types.SimpleNamespace(
            conv=conv, delta_rule=delta_rule, kda_mixer=kda_mixer,
            mla_mixer=mla_mixer, swiglu=swiglu, experts=experts,
            shared_expert=shared_expert, layer=layer, logits=logits,
            row_loss=row_loss)

    def reference_row(self, precision='float32'):
        """One packed row of one reference step: ``(params, buffers, grads so
        far, tokens [L], segment ids, 1 / the batch's target tokens, which
        held experts and whether the shared one are on, the row's fault flags)
        -> (grads so far + this row's, this row's weighted token losses)``."""
        import jax
        import jax.numpy as jnp
        row_loss = self.reference_parts(precision).row_loss

        def row(params, buffers, grads, tokens, seg, scale, expert_on, faults):
            (_, losses), new = jax.value_and_grad(row_loss, has_aux=True)(
                params, buffers, tokens, seg, scale, expert_on, faults)
            return jax.tree_util.tree_map(jnp.add, grads, new), losses
        return row

    def reference(self, key, batches, precision='float32', fault=None):
        """``len(batches)`` reference steps from the seed's weights, as
        ``lfm2-24b-a2b.py``'s.  Faults: ``'half_batch'`` leaves the second half
        of every batch's rows out; ``'missing_expert'`` one held expert's
        output; ``'missing_shared'`` the shared expert's; and ``ROW_FAULTS``
        (``reference_parts``)."""
        import jax
        import jax.numpy as jnp

        if precision not in self._jits:
            self._jits[precision] = jax.jit(self.reference_row(precision),
                                            donate_argnums=(2,))
        if 'init' not in self._jits:
            self._jits['init'] = jax.jit(
                lambda key: (self.init_params(key), self.init_buffers(key)))
            self._jits['zeros'] = jax.jit(
                lambda p: jax.tree_util.tree_map(jnp.zeros_like, p))
            self._jits['update'] = jax.jit(self.reference_update(),
                                           donate_argnums=(0, 1, 2))
            self._jits['change'] = jax.jit(lambda now, key: jax.tree_util.tree_map(
                lambda x, y: jnp.sqrt(jnp.sum(jnp.square(x - y))),
                now, self.init_params(key)))
        row, init, zeros, update, change = (self._jits[k] for k in (
            precision, 'init', 'zeros', 'update', 'change'))
        params, buffers = init(key)
        m, v = zeros(params), zeros(params)
        expert_on = np.ones(len(self.experts_held) + 1, np.float32)
        if fault == 'missing_expert':
            expert_on[0] = 0.0
        if fault == 'missing_shared':
            expert_on[-1] = 0.0
        faults = np.array([fault == name for name in ROW_FAULTS])
        losses, sample_losses, grad_norms = [], [], None
        for index, batch in enumerate(batches):
            n = len(batch['tokens']) // 2 if fault == 'half_batch' else None
            tokens, seg = batch['tokens'][:n], batch['segment_ids'][:n]
            last = np.concatenate([seg[:, 1:] != seg[:, :-1],
                                   np.ones_like(seg[:, :1], bool)], axis=1)
            targets = int(np.count_nonzero(~last & (seg != 0)))
            grads, token_losses = zeros(params), []
            for r in range(len(tokens)):
                grads, row_losses = row(
                    params, buffers, grads, tokens[r], seg[r],
                    np.float32(1.0 / max(targets, 1)), expert_on, faults)
                token_losses.append(np.asarray(row_losses, np.float64))
            token_losses = np.stack(token_losses)
            params, m, v, norms = update(params, m, v, grads, np.float32(index + 1))
            losses.append(float(token_losses.sum() / max(targets, 1)))
            sample_losses.append(packed.document_heads(token_losses, seg,
                                                       self.head_tokens))
            if grad_norms is None:
                grad_norms = jax.device_get(norms)
        return {'losses': losses, 'sample_losses': sample_losses,
                'grad_norms': grad_norms,
                'change_norms': jax.device_get(change(params, key))}

    def rehearsal_programs(self, key):
        import jax
        import jax.numpy as jnp
        shape = (self.batch, self.max_len)
        batch = {name: jax.ShapeDtypeStruct(shape, jnp.int32)
                 for name in ('tokens', 'segment_ids', 'positions', 'doc_ids')}
        state = jax.eval_shape(self.init_state, key)
        params, buffers = state[0], state[2]
        row = jax.ShapeDtypeStruct((self.max_len,), jnp.int32)
        scalar = jax.ShapeDtypeStruct((), jnp.float32)
        row_args = (params, buffers, params, row, row, scalar,
                    jax.ShapeDtypeStruct((len(self.experts_held) + 1,), jnp.float32),
                    jax.ShapeDtypeStruct((len(ROW_FAULTS),), jnp.bool_))
        return [('step', self.train_step(), (state, batch), (0,)),
                ('reference_row', self.reference_row(), row_args, (2,)),
                ('control_row', self.reference_row('fp8'), row_args, (2,)),
                ('reference_update', self.reference_update(),
                 (params, params, params, params, scalar), (0, 1, 2))]

    # -- yardstick: what the algorithm needs ------------------------------------

    def matrix_macs_per_token(self):
        """Multiply-accumulates of one token's forward pass through every
        matrix held here and through the delta rule, but the attention
        products: the mixers' projections and low-rank pairs, the delta rule
        in its chunked form (``kda_flops_per_token_head``), the dense
        feed-forward, the routers, the experts (``top_k`` a token over all
        ``num_experts``, of which the share ``held / num_experts`` falls here,
        taken at its expectation) with the shared expert, and the output
        head."""
        d, total = self.hidden, 0
        width = self.kda_heads * self.kda_head_dim
        held_share = len(self.experts_held) / self.num_experts
        for kind, dense in self.layers:
            if kind == 'kda':
                total += 3 * d * width + 2 * (d + width) * self.gate_rank \
                    + d * self.kda_heads + width * d \
                    + self.kda_heads * kda_flops_per_token_head(self.kda_head_dim) // 2
            else:
                total += d * self.heads * (self.nope + self.shared_key) \
                    + d * (self.kv_rank + self.shared_key) \
                    + self.kv_rank * self.heads * (self.nope + self.v_dim) \
                    + self.heads * self.v_dim * d
            total += 3 * d * self.d_ff if dense else \
                d * self.num_experts + 3 * d * self.d_shared \
                + self.top_k * held_share * 3 * d * self.d_expert
        return total + d * self.vocab

    def attention_layers(self):
        return sum(kind == 'mla' for kind, _ in self.layers)

    def needed_flops_per_sample(self):
        """Forward and backward (three passes of two operations a
        multiply-accumulate) of one packed row: every one of its ``max_len``
        positions through every matrix and the delta rule (padding included;
        recomputation is not counted), and latent attention's two products
        (scores over ``nope + shared_key``, values over ``v_dim``) over
        ``attention_pairs_per_row()`` for each head."""
        attention = self.attention_layers() * self.heads \
            * (self.nope + self.shared_key + self.v_dim) \
            * self.attention_pairs_per_row()
        return 3 * 2 * (self.max_len * self.matrix_macs_per_token() + attention)

    def parameter_count(self):
        return sum(int(np.prod(shape)) for shape, _ in
                   parameter_shapes(self).values())

    def flash_attention_needs(self):
        """(FLOPs, bytes) one step needs for attention inside documents,
        forward and backward, whatever implements it: the score product over
        ``nope + shared_key`` = 192 and the value product over ``v_dim`` = 128
        forward, four products backward (twice the forward's), over the
        needed pairs of every head; q and k at 192, v and the output at 128,
        each read or written once forward, and with their cotangents once
        more backward."""
        pairs = self.batch * self.attention_pairs_per_row()
        qk, v = self.nope + self.shared_key, self.v_dim
        flops = self.attention_layers() * 3 * 2 * self.heads * (qk + v) * pairs
        one_pass = self.batch * self.max_len * self.heads * (2 * qk + 2 * v)
        return flops, self.attention_layers() * 2 * 3 * one_pass

    def kda_scan_needs(self):
        """(FLOPs, bytes) one step needs for the delta rule of all KDA layers
        (the recurrence alone: not the projections, the convolutions or the
        gates around it), by the chunked form's matrix products at chunks of
        64, whatever implements it.

        FLOPs: ``tokens x heads x kda_flops_per_token_head`` (forward: 2 x
        128 x 64 x 5 + 3 x 2 x 128 x 128 = 180,224 at the published head) x 4:
        forward, the recomputed forward, and a backward of twice the forward.
        Bytes: q, k, v and o in the compute dtype, g and beta in float32 and
        the chunk states (``tokens / 64`` of ``heads x 128 x 128`` float32),
        each once a pass, for one pass each way."""
        tokens = self.batch * self.max_len
        layers = sum(kind == 'kda' for kind, _ in self.layers)
        heads, hd = self.kda_heads, self.kda_head_dim
        flops = layers * 4 * tokens * heads * kda_flops_per_token_head(hd)
        itemsize = 2 if self.compute_dtype == 'bfloat16' else 4
        one_pass = tokens * heads * (4 * hd * itemsize + 4 * hd + 4) \
            + tokens // KDA_CHUNK * heads * hd * hd * 4
        return flops, layers * 2 * one_pass
