"""``lfm2-24b-a2b``: everything that belongs to this configuration alone.

The harness loads this file by the configuration's name and talks to
:class:`Config` only (see ``resnet50-imagenet.py`` for the three parts).  The
chip holds one chip's share of an expert-parallel job in which 8 chips share
each layer: 8 of the 64 experts of every expert layer, 1/8 of the vocabulary,
and published layers 1-5 (``layers_here``).  Every width is as published.

* The system under test: documents of varying length in petastorm's Unischema
  Parquet, ``make_reader`` -> ``PackedDataLoader`` (``id_field``: the ids ride
  to the device beside the tokens) -> the jitted AdamW step around
  ``petastorm_tpu.models.transformer.TransformerLM`` with its layer pattern,
  ``ops.flash_attention`` and ``models.moe.moe_share_apply``.
* The yardstick: the plain float32 reference of the same steps.  It imports
  nothing of ``petastorm_tpu``; it is handed the same DOCUMENTS, packs them
  its own way (in the order given, each row filled while the next document
  fits) and is compared one document at a time, by the loss of the tokens
  with which each document begins: what lies before a document in its row
  reaches those first if the convolution's taps, the attention's mask or the
  positions do not stop at the boundary.
"""

import io
import os

import numpy as np

import oracle

NO_DOCUMENT = -1


# -- shapes of the network, shared by the weights, the reference and the FLOPs

def parameter_shapes(c):
    """{path: (shape, initialiser)} of every parameter held here, named as
    flax names the modules of ``TransformerLM``.  Initialisers: ``'fan_in'``
    normal(0, 1 / fan_in) with the fan-in the leading axes of a projection
    (the axis before the last for a stack of expert matrices), ``'ones'``,
    ``('normal', std)``."""
    d, heads, kv, hd = c.hidden, c.heads, c.kv_heads, c.head_dim
    held = len(c.experts_held)
    shapes = {('embed', 'embedding'): ((c.vocab, d), ('normal', 0.02)),
              ('ln_f', 'scale'): ((d,), 'ones')}
    for i, (kind, dense) in enumerate(c.layers):
        block = 'block_%d' % i
        shapes[block, 'ln1', 'scale'] = ((d,), 'ones')
        shapes[block, 'ln2', 'scale'] = ((d,), 'ones')
        if kind == 'conv':
            shapes[block, 'conv', 'in_proj', 'kernel'] = ((d, 3 * d), 'fan_in')
            shapes[block, 'conv', 'conv'] = (
                (c.conv_kernel, d), ('normal', 1.0 / np.sqrt(c.conv_kernel)))
            shapes[block, 'conv', 'out_proj', 'kernel'] = ((d, d), 'fan_in')
        else:
            shapes[block, 'attn', 'q', 'kernel'] = ((d, heads, hd), 'fan_in')
            shapes[block, 'attn', 'kv', 'kernel'] = ((d, 2, kv, hd), 'fan_in')
            shapes[block, 'attn', 'q_norm', 'scale'] = ((hd,), 'ones')
            shapes[block, 'attn', 'k_norm', 'scale'] = ((hd,), 'ones')
            shapes[block, 'attn', 'out', 'kernel'] = ((heads, hd, d), 'fan_out_in')
        if dense:
            shapes[block, 'w1', 'kernel'] = ((d, c.d_ff), 'fan_in')
            shapes[block, 'w3', 'kernel'] = ((d, c.d_ff), 'fan_in')
            shapes[block, 'w2', 'kernel'] = ((c.d_ff, d), 'fan_in')
        else:
            shapes[block, 'moe', 'router'] = ((d, c.num_experts), 'fan_in')
            shapes[block, 'moe', 'w1'] = ((held, d, c.d_expert), 'expert')
            shapes[block, 'moe', 'w3'] = ((held, d, c.d_expert), 'expert')
            shapes[block, 'moe', 'w2'] = ((held, c.d_expert, d), 'expert')
    return shapes


def fan_in_of(shape, kind):
    if kind == 'expert':
        return shape[-2]
    if kind == 'fan_out_in':            # [heads, head_dim, d]: all but the last
        return int(np.prod(shape[:-1]))
    return shape[0]


def power_law_ids(rng, rows, exponent, n):
    """``n`` ids in [0, rows) with P(id = k) ~ (k + 1) ** -exponent (inverse
    CDF of the continuous law on [1, rows + 1))."""
    u = rng.random(n)
    a = 1.0 - exponent
    x = ((float(rows + 1) ** a - 1.0) * u + 1.0) ** (1.0 / a)
    return np.minimum(x.astype(np.int64) - 1, rows - 1).astype(np.int32)


def document_lengths(rng, n, median, sigma, shortest, longest):
    return np.clip(np.rint(rng.lognormal(np.log(median), sigma, n)),
                   shortest, longest).astype(np.int64)


def starts_of(segment_ids):
    """True on the first token of every document of packed rows."""
    before = np.concatenate([np.zeros_like(segment_ids[:, :1]),
                             segment_ids[:, :-1]], axis=1)
    return (segment_ids != 0) & (segment_ids != before)


def document_heads(token_losses, segment_ids, head_tokens):
    """``head_tokens`` losses a document of packed rows, in the order the
    documents lie: ``token_losses`` (0 where a token has no target) of each
    document's first tokens, 0 where it has no such token."""
    rows, at = np.nonzero(starts_of(segment_ids))
    heads = np.zeros((len(at), head_tokens))
    for j in range(head_tokens):
        ahead = np.minimum(at + j, segment_ids.shape[1] - 1)
        same = (at + j == ahead) & (segment_ids[rows, ahead] == segment_ids[rows, at])
        heads[:, j] = np.where(same, token_losses[rows, ahead], 0.0)
    return heads.reshape(-1)


def pack_in_order(documents, ids, max_len):
    """The yardstick's own packing: the documents in the order given, each row
    filled while the next document fits.  Returns the four leaves."""
    rows, room = [[]], max_len
    for doc, doc_id in zip(documents, ids):
        if len(doc) > room:
            rows.append([])
            room = max_len
        rows[-1].append((doc, doc_id))
        room -= len(doc)
    batch = {'tokens': np.zeros((len(rows), max_len), np.int32),
             'segment_ids': np.zeros((len(rows), max_len), np.int32),
             'positions': np.zeros((len(rows), max_len), np.int32),
             'doc_ids': np.full((len(rows), max_len), NO_DOCUMENT, np.int32)}
    for r, row in enumerate(rows):
        at = 0
        for s, (doc, doc_id) in enumerate(row):
            span = slice(at, at + len(doc))
            batch['tokens'][r, span] = doc
            batch['segment_ids'][r, span] = s + 1
            batch['positions'][r, span] = np.arange(len(doc))
            batch['doc_ids'][r, span] = doc_id
            at += len(doc)
    return batch


class DeliveredIds(object):
    """The ids of the documents a batch holds, read from its fixed-shape
    leaves only when converted (``np.asarray``): the timed loop keeps these
    by reference and never waits for the device."""

    def __init__(self, batch):
        self.doc_ids, self.segment_ids = batch['doc_ids'], batch['segment_ids']

    def __array__(self, dtype=None, copy=None):
        ids = np.asarray(self.doc_ids)[starts_of(np.asarray(self.segment_ids))]
        return ids if dtype is None else ids.astype(dtype)


class Config(object):
    def __init__(self, spec, tiny=False, **sizes_for_a_reading):
        """``sizes_for_a_reading``: a smaller dataset for ``read_limits.py``,
        which needs the first steps only; a run never passes any."""
        self.spec = spec
        sizes = {k: v for k, v in spec.items() if not isinstance(v, (dict, list))}
        sizes.update(spec['dataset'], experts_held=spec['experts_held'],
                     published_num_experts=spec['published']['num_experts'])
        if tiny:
            sizes.update(spec['tiny'])
        sizes.update(sizes_for_a_reading)
        self.hidden = sizes['hidden_size']
        self.heads = sizes['num_attention_heads']
        self.kv_heads = sizes['num_key_value_heads']
        self.head_dim = self.hidden // self.heads
        self.d_ff = sizes['intermediate_size']
        self.d_expert = sizes['moe_intermediate_size']
        self.num_experts = sizes['published_num_experts']    # the router's width
        self.experts_held = tuple(sizes['experts_held'])
        self.top_k = sizes['num_experts_per_tok']
        self.route_scale = float(sizes['routed_scaling_factor'])
        self.vocab = sizes['vocab_size']
        self.conv_kernel = sizes['conv_L_cache']
        #: the tokens of a document that a tap reaching back over its start
        #: would touch: ``sample_loss`` holds the loss of each of these
        self.head_tokens = self.conv_kernel - 1
        self.norm_eps = sizes['norm_eps']
        self.rope_theta = float(spec['rope_parameters']['rope_theta'])
        #: (mixer, has the dense feed-forward) of each layer held here
        self.layers = [(spec['layer_types'][i], i < spec['num_dense_layers'])
                       for i in spec['layers_here']]
        assert len(self.layers) == spec['num_hidden_layers']
        assert len(spec['experts_held']) == spec['num_experts']
        self.batch = sizes['batch']                  # packed rows a step
        self.max_len = sizes['max_len']
        self.rows = sizes['documents']               # stored rows: documents
        self.rows_per_rowgroup = sizes['rows_per_rowgroup']
        self.length_law = (sizes['length_median'], sizes['length_sigma'],
                           sizes['length_min'], self.max_len)
        #: fixes the documents' lengths and the order the reader delivers
        #: them in: part of the configuration, the same in every run
        self.layout_seed = sizes['layout_seed']
        self.zipf = sizes['token_zipf_exponent']
        self.compute_dtype = sizes['compute_dtype']
        self.optimizer = spec['optimizer']
        self.donate_state = True
        self._jits = {}

    # -- dataset ---------------------------------------------------------------

    def _schema(self):
        from petastorm_tpu.codecs import NdarrayCodec
        from petastorm_tpu.unischema import Unischema, UnischemaField
        return Unischema('PackedDocuments', [
            UnischemaField('doc_id', np.int32, (), None, False),
            UnischemaField('tokens', np.int32, (None,), NdarrayCodec(), False)])

    def write_dataset(self, path, seed):
        """``rows`` documents: lengths from the configuration's
        ``layout_seed`` (the same in every run), token ids from ``seed``, both
        in bulk; one ``np.save`` cell a document, written with pyarrow inside
        the package's own ``materialize_dataset_pyarrow`` stamp."""
        import pyarrow as pa
        import pyarrow.parquet as pq
        from petastorm_tpu.etl.dataset_metadata import materialize_dataset_pyarrow

        # the two streams a seed had before PR 33: layout_seed k draws the
        # lengths that --seed k drew then
        lengths_seed = np.random.SeedSequence(self.layout_seed).spawn(2)[0]
        tokens_seed = np.random.SeedSequence(seed).spawn(2)[1]
        lengths = document_lengths(np.random.default_rng(lengths_seed), self.rows,
                                   *self.length_law)
        tokens = power_law_ids(np.random.default_rng(tokens_seed), self.vocab,
                               self.zipf, int(lengths.sum()))
        schema = self._schema()
        field = schema.fields['tokens']
        cells = [bytes(field.codec.encode(field, doc))
                 for doc in np.split(tokens, np.cumsum(lengths)[:-1])]
        arrow = schema.as_arrow_schema()
        os.makedirs(path, exist_ok=True)
        rows_per_file = self.rows_per_rowgroup * 8
        with materialize_dataset_pyarrow('file://' + path, schema):
            for part, start in enumerate(range(0, self.rows, rows_per_file)):
                stop = min(start + rows_per_file, self.rows)
                pq.write_table(pa.table(
                    {'doc_id': pa.array(np.arange(start, stop, dtype=np.int32)),
                     'tokens': pa.array(cells[start:stop],
                                        arrow.field('tokens').type)},
                    schema=arrow),
                    os.path.join(path, 'part_%05d.parquet' % part),
                    row_group_size=self.rows_per_rowgroup)

    def open_reader(self, url, seed, num_epochs):
        """Row groups shuffled by ``layout_seed``, not by the run's ``seed``:
        every run packs the same lengths in the same order."""
        from petastorm_tpu import make_reader
        return make_reader(url, num_epochs=num_epochs,
                           seed=self.layout_seed % (2 ** 31))

    def loader_kwargs(self):
        return {'tokens_field': 'tokens', 'id_field': 'doc_id',
                'max_len': self.max_len}

    # -- weights, from the seed, in one jitted call ----------------------------

    def init_params(self, key):
        import jax
        import jax.numpy as jnp
        params = {}
        for index, (path, (shape, kind)) in enumerate(
                sorted(parameter_shapes(self).items())):
            if kind == 'ones':
                leaf = jnp.ones(shape, jnp.float32)
            else:
                std = kind[1] if isinstance(kind, tuple) \
                    else 1.0 / np.sqrt(fan_in_of(shape, kind))
                leaf = np.float32(std) * jax.random.normal(
                    jax.random.fold_in(key, index), shape, jnp.float32)
            node = params
            for name in path[:-1]:
                node = node.setdefault(name, {})
            node[path[-1]] = leaf
        return params

    def init_buffers(self, key):
        """The selection bias of every expert layer: a buffer, held fixed."""
        import jax
        import jax.numpy as jnp
        return {'block_%d' % i: {'moe': {'expert_bias': np.float32(0.01)
                                         * jax.random.normal(
            jax.random.fold_in(key, 10_000 + i), (self.num_experts,), jnp.float32)}}
            for i, (_, dense) in enumerate(self.layers) if not dense}

    def _tx(self):
        import optax
        o = self.optimizer
        return optax.adamw(o['learning_rate'], b1=o['b1'], b2=o['b2'], eps=o['eps'],
                           weight_decay=o['weight_decay'])

    def init_state(self, key):
        """(params, AdamW's state, buffers) for the program's step."""
        params = self.init_params(key)
        return params, self._tx().init(params), self.init_buffers(key)

    # -- the program's step ----------------------------------------------------

    def model(self):
        import jax.numpy as jnp
        from petastorm_tpu.models.transformer import TransformerLM
        from petastorm_tpu.ops import flash_attention
        return TransformerLM(
            vocab_size=self.vocab, d_model=self.hidden, num_heads=self.heads,
            num_layers=len(self.layers), d_ff=self.d_ff, max_seq_len=self.max_len,
            dtype=jnp.dtype(self.compute_dtype), attn_fn=flash_attention,
            remat=True, num_kv_heads=self.kv_heads, pos_embed='rope',
            layer_types=tuple(kind for kind, _ in self.layers), ffn='moe',
            num_dense_layers=sum(dense for _, dense in self.layers),
            moe={'num_experts': self.num_experts, 'top_k': self.top_k,
                 'd_expert': self.d_expert, 'experts_held': self.experts_held,
                 'scale': self.route_scale},
            rope_base=self.rope_theta, qk_norm=True, norm_eps=self.norm_eps,
            use_bias=False, conv_kernel=self.conv_kernel)

    def train_step(self):
        """One AdamW step on a packed batch: next-token loss inside each
        document (``packing.next_token_targets``), the batch's loss the mean
        over its target tokens.  ``sample_loss`` holds ``head_tokens`` losses a
        document, those of its first tokens (see ``assumed.sample_loss``)."""
        import jax
        import jax.numpy as jnp
        import optax
        from petastorm_tpu.jax import packing

        model, tx = self.model(), self._tx()

        def document_heads(token_loss, segment_ids):
            """``head_tokens`` losses a document, those of its first tokens (0
            where it has no such token or that token no target), the real
            documents first and in the order they lie in the batch."""
            heads = []
            for j in range(self.head_tokens):
                def ahead(x):
                    return jnp.pad(x[:, j:], ((0, 0), (0, j)))
                heads.append(jnp.where(ahead(segment_ids) == segment_ids,
                                       ahead(token_loss), 0.0))
            heads = jnp.stack(heads, axis=-1).reshape(-1, self.head_tokens)
            real = packing.document_starts(segment_ids).reshape(-1)
            return jnp.where(real[:, None], heads, 0.0)[
                jnp.argsort(~real, stable=True)].reshape(-1)

        def step(state, batch):
            params, opt_state, buffers = state
            tokens, segment_ids = batch['tokens'], batch['segment_ids']
            targets, weights = packing.next_token_targets(tokens, segment_ids)

            def loss_fn(p):
                logits, sown = model.apply(
                    {'params': p, 'buffers': buffers}, tokens, batch['positions'],
                    segment_ids, mutable=['diagnostics'])
                with jax.named_scope('pt/lm_head_loss'):
                    token_loss = weights * \
                        optax.softmax_cross_entropy_with_integer_labels(
                            logits, targets)
                    loss = token_loss.sum() / jnp.maximum(weights.sum(), 1.0)
                return loss, (token_loss, sown.get('diagnostics', {}))

            (loss, (token_loss, routed)), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params)
            updates, new_opt = tx.update(grads, opt_state, params)
            # what each expert layer routed where: {layer: {'tokens_per_expert':
            # [held], 'held_share': of all assignments}}, for diagnostics
            routed = {name: {k: v[0] for k, v in layer['moe'].items()}
                      for name, layer in routed.items()}
            return (optax.apply_updates(params, updates), new_opt, buffers), \
                {'loss': loss, 'routed': routed,
                 'sample_loss': document_heads(token_loss, segment_ids)}
        return step

    def row_ids(self, batch):
        return DeliveredIds(batch)

    def params_of(self, state):
        return state[0]

    def first_gradient(self, state_after_one_step, key):
        """Adam's first moment after one step is ``(1 - b1)`` times the first
        gradient."""
        import jax
        scale = np.float32(1.0 / (1.0 - self.optimizer['b1']))
        return jax.tree_util.tree_map(lambda m: m * scale,
                                      state_after_one_step[1][0].mu)

    # -- yardstick: what the Parquet files hold ---------------------------------

    def stored_rows(self, path, ids):
        """{document id: tokens} of ``ids``, from the files by pyarrow and
        ``np.load`` alone."""
        import pyarrow.dataset as ds
        wanted = sorted(set(int(i) for i in ids))
        table = ds.dataset(path, format='parquet').to_table(
            columns=['doc_id', 'tokens'], filter=ds.field('doc_id').isin(wanted))
        return {doc_id: np.load(io.BytesIO(cell), allow_pickle=False)
                for doc_id, cell in zip(table.column('doc_id').to_pylist(),
                                        table.column('tokens').to_pylist())}

    def all_row_ids(self, path):
        import pyarrow.parquet as pq
        return np.asarray(pq.read_table(path, columns=['doc_id'])
                          .column('doc_id').to_numpy())

    def delivered_error(self, path, delivered):
        """Largest difference, over all four leaves, between a delivered batch
        (host copy) and the batch that the stored documents of the ids it
        names give when laid where it says they lie: tokens by document and
        offset, and the segment ids, positions and ids they imply.  Exact: 0."""
        delivered = {k: np.asarray(v) for k, v in delivered.items()}
        segment_ids, doc_ids = delivered['segment_ids'], delivered['doc_ids']
        starts = starts_of(segment_ids)
        stored = self.stored_rows(path, doc_ids[starts])
        want = {'tokens': np.zeros_like(delivered['tokens']),
                'segment_ids': np.zeros_like(segment_ids),
                'positions': np.zeros_like(delivered['positions']),
                'doc_ids': np.full_like(doc_ids, NO_DOCUMENT)}
        for r in range(len(segment_ids)):
            for s, at in enumerate(np.flatnonzero(starts[r])):
                doc = stored.get(int(doc_ids[r, at]))
                if doc is None or at + len(doc) > self.max_len:
                    return float('inf')     # no such document, or it cannot lie here
                span = slice(at, at + len(doc))
                want['tokens'][r, span] = doc
                want['segment_ids'][r, span] = s + 1
                want['positions'][r, span] = np.arange(len(doc))
                want['doc_ids'][r, span] = doc_ids[r, at]
        return max(float(np.max(np.abs(delivered[name].astype(np.int64)
                                       - want[name]))) for name in want)

    def reference_batches(self, path, ids_per_step):
        """The first steps' documents as the files hold them, packed the
        yardstick's own way."""
        stored = self.stored_rows(path, np.concatenate(ids_per_step))
        return [pack_in_order([stored[int(i)] for i in ids], ids, self.max_len)
                for ids in ids_per_step]

    # -- yardstick: the plain reference -----------------------------------------

    def reference_parts(self, precision='float32'):
        """Plain ``jax.numpy`` LFM2 share in float32 at ``highest`` matmul
        precision, over ONE packed row (``[L]`` tokens, segment ids,
        positions) so that it fits at the published widths; the parts by name
        (``conv_mixer``, ``attention_mixer``, ``swiglu``, ``experts``,
        ``logits``, ``row_loss``), which the tests compare one by one.  Dense
        attention under an explicit document mask, one head at a time; the
        convolution as three shifted multiplies; the experts as a loop over
        the held ids.

        ``precision='fp8'`` is the control: the operands of every matrix
        product rounded to float8_e4m3fn under a per-tensor scale (cotangents
        to bfloat16); ``'bf16'`` rounds the same operands and their cotangents
        to bfloat16, the stated precision's own rounding.  The router stays in
        float32 in all three, as in the program (``assumed.router_precision``).
        """
        import types

        import jax
        import jax.numpy as jnp
        from jax import lax

        highest = lax.Precision.HIGHEST
        q = oracle.operand_rounding(precision)
        eps, top_k = np.float32(self.norm_eps), self.top_k
        heads, group = self.heads, self.heads // self.kv_heads
        half = self.head_dim // 2
        frequencies = self.rope_theta ** (-np.arange(half, dtype=np.float32) / half)

        def mm(a, b):
            return jnp.dot(q(a), q(b), precision=highest)

        def rms(x, scale):
            return x * lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + eps) \
                * scale

        def shifted(x, k):
            return jnp.pad(x, ((k, 0),) + ((0, 0),) * (x.ndim - 1))[:len(x)]

        def conv_mixer(p, h, seg, leaking):
            b, c, x = jnp.split(mm(h, p['in_proj']['kernel']), 3, axis=-1)
            u = b * x
            out = p['conv'][0] * u
            for k in range(1, self.conv_kernel):
                inside = (seg == shifted(seg, k)) & (seg != 0)
                # the planted fault: the document mask never reaches the
                # convolution, so every tap reaches whatever lies before it
                inside |= leaking & (jnp.arange(len(seg)) >= k)
                out = out + p['conv'][k] * jnp.where(inside[:, None], shifted(u, k), 0)
            return mm(c * out, p['out_proj']['kernel'])

        def rotate(x, positions):                    # [L, heads, head_dim]
            angles = positions[:, None].astype(jnp.float32) * frequencies
            cos, sin = jnp.cos(angles)[:, None, :], jnp.sin(angles)[:, None, :]
            x1, x2 = x[..., :half], x[..., half:]
            return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)

        def attention_mixer(p, h, seg, positions):
            d = h.shape[-1]
            qs = mm(h, p['q']['kernel'].reshape(d, -1)).reshape(-1, heads, 2 * half)
            kv = mm(h, p['kv']['kernel'].reshape(d, -1)).reshape(
                -1, 2, self.kv_heads, 2 * half)
            qs = rotate(rms(qs, p['q_norm']['scale']), positions)
            ks = rotate(rms(kv[:, 0], p['k_norm']['scale']), positions)
            at = jnp.arange(len(seg))
            mask = (seg[:, None] == seg[None, :]) & (seg[:, None] != 0) \
                & (at[None, :] <= at[:, None])
            scale = np.float32(1.0 / np.sqrt(2 * half))

            @jax.checkpoint
            def one_head(args):
                qh, kh, vh = args
                scores = jnp.where(mask, mm(qh, kh.T) * scale, -jnp.inf)
                scores = jnp.where(mask.any(-1, keepdims=True), scores, 0.0)
                weights = jnp.where(mask, jax.nn.softmax(scores, axis=-1), 0.0)
                return mm(weights, vh)
            out = lax.map(one_head, (
                jnp.moveaxis(qs, 1, 0),
                jnp.repeat(jnp.moveaxis(ks, 1, 0), group, axis=0),
                jnp.repeat(jnp.moveaxis(kv[:, 1], 1, 0), group, axis=0)))
            return mm(jnp.moveaxis(out, 0, 1).reshape(len(seg), -1),
                      p['out']['kernel'].reshape(-1, d))

        def swiglu(h, w1, w3, w2):
            return mm(jax.nn.silu(mm(h, w1)) * mm(h, w3), w2)

        def experts(p, bias, h, expert_on, held=self.experts_held):
            """What the experts ``held`` (global ids, in the order of the
            matrices' leading axis) add for the tokens ``h``."""
            scores = jax.nn.sigmoid(jnp.dot(h, p['router'], precision=highest))
            _, chosen = lax.top_k(lax.stop_gradient(scores + bias), top_k)
            picked = jnp.take_along_axis(scores, chosen, axis=-1)
            weights = picked / (jnp.sum(picked, -1, keepdims=True)
                                + np.float32(1e-6)) * np.float32(self.route_scale)
            out = jnp.zeros_like(h)
            for j, expert in enumerate(held):
                weight = jnp.sum(jnp.where(chosen == expert, weights, 0.0), -1)
                out = out + (weight * expert_on[j])[:, None] * swiglu(
                    h, p['w1'][j], p['w3'][j], p['w2'][j])
            return out

        def layer(index, p, bias, x, seg, positions, expert_on, leaking):
            kind, dense = self.layers[index]
            h = rms(x, p['ln1']['scale'])
            x = x + (conv_mixer(p['conv'], h, seg, leaking) if kind == 'conv'
                     else attention_mixer(p['attn'], h, seg, positions))
            h = rms(x, p['ln2']['scale'])
            if dense:
                return x + swiglu(h, p['w1']['kernel'], p['w3']['kernel'],
                                  p['w2']['kernel'])
            return x + experts(p['moe'], bias, h, expert_on)

        def logits(params, buffers, tokens, seg, positions, expert_on, leaking):
            x = params['embed']['embedding'][tokens]
            for index in range(len(self.layers)):
                name = 'block_%d' % index
                bias = buffers[name]['moe']['expert_bias'] if name in buffers else None
                # one layer's activations at a time
                x = jax.checkpoint(layer, static_argnums=(0,))(
                    index, params[name], bias, x, seg, positions, expert_on, leaking)
            return mm(rms(x, params['ln_f']['scale']), params['embed']['embedding'].T)

        def row_loss(params, buffers, tokens, seg, positions, scale, expert_on,
                     leaking):
            """(the row's share of the batch's loss, its weighted token losses)."""
            row_logits = logits(params, buffers, tokens, seg, positions, expert_on,
                                leaking)
            targets = jnp.concatenate([tokens[1:], tokens[:1]])
            weights = ((seg == jnp.concatenate([seg[1:], jnp.zeros_like(seg[:1])]))
                       & (seg != 0)).astype(jnp.float32)
            losses = weights * (
                jax.nn.logsumexp(row_logits, axis=-1)
                - jnp.take_along_axis(row_logits, targets[:, None], 1)[:, 0])
            return jnp.sum(losses) * scale, losses
        return types.SimpleNamespace(
            conv_mixer=conv_mixer, attention_mixer=attention_mixer, swiglu=swiglu,
            experts=experts, logits=logits, row_loss=row_loss)

    def reference_row(self, precision='float32'):
        """One packed row of one reference step: ``(params, buffers, grads so
        far, tokens [L], segment ids, positions, 1 / the batch's target tokens,
        which held experts are on, whether the taps leak) -> (grads so far +
        this row's, this row's weighted token losses)``."""
        import jax
        import jax.numpy as jnp
        row_loss = self.reference_parts(precision).row_loss

        def row(params, buffers, grads, tokens, seg, positions, scale, expert_on,
                leaking):
            (_, losses), new = jax.value_and_grad(row_loss, has_aux=True)(
                params, buffers, tokens, seg, positions, scale, expert_on, leaking)
            return jax.tree_util.tree_map(jnp.add, grads, new), losses
        return row

    def reference_update(self):
        """AdamW written out: ``(params, m, v, grads, step) -> (params, m, v,
        the gradient's norm leaf by leaf)``."""
        import jax
        import jax.numpy as jnp
        o = self.optimizer
        lr, b1, b2, eps, decay = (np.float32(o[k]) for k in (
            'learning_rate', 'b1', 'b2', 'eps', 'weight_decay'))

        def update(params, m, v, grads, step):
            tree = jax.tree_util.tree_map
            m = tree(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
            v = tree(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
            m_scale, v_scale = 1 / (1 - b1 ** step), 1 / (1 - b2 ** step)
            params = tree(lambda p, m, v: p - lr * (
                m * m_scale / (jnp.sqrt(v * v_scale) + eps) + decay * p), params, m, v)
            return params, m, v, tree(lambda g: jnp.sqrt(jnp.sum(jnp.square(g))), grads)
        return update

    def reference(self, key, batches, precision='float32', fault=None):
        """``len(batches)`` reference steps from the seed's weights: per-step
        losses, the losses of each document's first tokens
        (``document_heads``), and leaf by leaf the norm of the first gradient
        and of the parameters' change.  Faults:
        ``'half_batch'`` leaves the second half of every batch's rows out;
        ``'missing_expert'`` leaves one held expert's output out;
        ``'leaking_tap'`` is a convolution that was never handed the segment
        ids: at every document boundary of every row its taps reach into the
        document before."""
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
        expert_on = np.ones(len(self.experts_held), np.float32)
        if fault == 'missing_expert':
            expert_on[0] = 0.0
        losses, sample_losses, grad_norms = [], [], None
        for index, batch in enumerate(batches):
            n = len(batch['tokens']) // 2 if fault == 'half_batch' else None
            tokens, seg, positions = (batch[k][:n] for k in (
                'tokens', 'segment_ids', 'positions'))
            last = np.concatenate([seg[:, 1:] != seg[:, :-1],
                                   np.ones_like(seg[:, :1], bool)], axis=1)
            targets = int(np.count_nonzero(~last & (seg != 0)))
            grads, token_losses = zeros(params), []
            for r in range(len(tokens)):
                grads, row_losses = row(
                    params, buffers, grads, tokens[r], seg[r], positions[r],
                    np.float32(1.0 / max(targets, 1)), expert_on,
                    np.bool_(fault == 'leaking_tap'))
                token_losses.append(np.asarray(row_losses, np.float64))
            token_losses = np.stack(token_losses)
            params, m, v, norms = update(params, m, v, grads, np.float32(index + 1))
            losses.append(float(token_losses.sum() / max(targets, 1)))
            sample_losses.append(document_heads(token_losses, seg, self.head_tokens))
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
        row_args = (params, buffers, params, row, row, row, scalar,
                    jax.ShapeDtypeStruct((len(self.experts_held),), jnp.float32),
                    jax.ShapeDtypeStruct((), jnp.bool_))
        return [('step', self.train_step(), (state, batch), (0,)),
                ('reference_row', self.reference_row(), row_args, (2,)),
                ('control_row', self.reference_row('fp8'), row_args, (2,)),
                ('reference_update', self.reference_update(),
                 (params, params, params, params, scalar), (0, 1, 2))]

    # -- yardstick: what the algorithm needs ------------------------------------

    def matrix_macs_per_token(self):
        """Multiply-accumulates of one token's forward pass through every
        matrix held here, but the attention products: mixers' projections,
        the dense feed-forward, the routers, the experts (``top_k`` a token
        over all ``num_experts``, of which the share ``held / num_experts``
        falls here, taken at its expectation), and the output head."""
        d, total = self.hidden, 0
        held_share = len(self.experts_held) / self.num_experts
        for kind, dense in self.layers:
            total += 3 * d * d + d * d if kind == 'conv' \
                else 2 * d * d + 2 * d * self.kv_heads * self.head_dim
            total += 3 * d * self.d_ff if dense else \
                d * self.num_experts + self.top_k * held_share * 3 * d * self.d_expert
        return total + d * self.vocab

    def expected_length_moments(self):
        """(E[L], E[L (L + 1) / 2]) of the clipped log-normal document length,
        by quadrature over the law's normal variable."""
        median, sigma, shortest, longest = self.length_law
        z = np.linspace(-8.0, 8.0, 32001)
        weight = np.exp(-0.5 * z * z)
        weight /= weight.sum()
        lengths = np.clip(median * np.exp(sigma * z), shortest, longest)
        return float(weight @ lengths), float(weight @ (lengths * (lengths + 1) / 2))

    def attention_pairs_per_row(self):
        """Query-key pairs that causal attention inside each document needs
        in one packed row, at their expectation under the length law: a row
        of ``max_len`` tokens holds ``max_len / E[L]`` documents of ``E[L (L +
        1) / 2]`` pairs each.  Pairs across documents, which a kernel may
        visit and mask, are not needed and not counted."""
        mean, pairs = self.expected_length_moments()
        return self.max_len / mean * pairs

    def attention_layers(self):
        return sum(kind != 'conv' for kind, _ in self.layers)

    def needed_flops_per_sample(self):
        """Forward and backward (three passes of two operations a
        multiply-accumulate) of one packed row: every one of its ``max_len``
        positions through every matrix (padding included: the loader's
        ``padding_waste_pct`` says how much that is; recomputation is not
        counted), and the attention layers' two products (scores, values)
        over ``attention_pairs_per_row()`` for each head."""
        attention = self.attention_layers() * 2 * self.heads * self.head_dim \
            * self.attention_pairs_per_row()
        return 3 * 2 * (self.max_len * self.matrix_macs_per_token() + attention)

    def parameter_count(self):
        return sum(int(np.prod(shape)) for shape, _ in
                   parameter_shapes(self).values())

    def needed_bytes_per_step(self):
        """What one step has to move whatever implements it: parameters and
        Adam's two moments (float32) each read and written once, the gradient
        written and read once, and the batch's four int32 leaves.
        Activations are the implementation's."""
        return 4 * (3 * 2 + 2) * self.parameter_count() \
            + 4 * 4 * self.batch * self.max_len

    def expert_ffn_needs(self):
        """(FLOPs, bytes) one step needs for the grouped expert PRODUCTS of
        all expert layers, forward and backward, and for nothing around them
        (the sort, the gathers and the combine are not counted, as
        ``expert_ffn_ms`` does not time them): the three SwiGLU matrices over
        the assignments that fall on held experts, the held matrices read once
        a pass in the compute dtype and their gradient written once.  The
        assignments are an EXPECTATION, ``tokens x top_k x held /
        num_experts``: a reader is handed no output of the step, so the count
        the step did (its ``routed`` output) cannot stand here, and the share
        reads high by as much as the step's held share lies under ``held /
        num_experts`` (PERF.md section 7)."""
        tokens = self.batch * self.max_len
        layers = sum(not dense for _, dense in self.layers)
        assignments = tokens * self.top_k * len(self.experts_held) / self.num_experts
        matrices = 3 * self.hidden * self.d_expert
        flops = layers * 3 * 2 * assignments * matrices
        itemsize = 2 if self.compute_dtype == 'bfloat16' else 4
        return flops, layers * itemsize * 3 * len(self.experts_held) * matrices

    def flash_attention_needs(self):
        """(FLOPs, bytes) one step needs for attention inside documents,
        forward and backward, whatever implements it: two products forward
        and four backward over the needed pairs of every head; q and the
        output at ``heads``, k and v at ``kv_heads``, each read or written
        once forward, and with their cotangents once more backward."""
        pairs = self.batch * self.attention_pairs_per_row()
        flops = self.attention_layers() * (2 + 4) * 2 * self.heads \
            * self.head_dim * pairs
        tokens = self.batch * self.max_len
        one_pass = tokens * self.head_dim * (2 * self.heads + 2 * self.kv_heads)
        return flops, self.attention_layers() * 2 * 3 * one_pass
