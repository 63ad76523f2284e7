"""Variable-length documents -> packed fixed-shape LM batches.

The packing showcase: documents of *different* lengths land in Parquet
(wildcard-shape ``tokens`` field), the reader streams them per-row, and
``petastorm_tpu.jax.packing`` lays them end-to-end into static
``(rows, max_len)`` batches with segment ids — so XLA compiles ONE program
and pad-token FLOPs are mostly recovered.  Attention stays correct across
document boundaries via ``packed_attention``'s segment mask, and the loss
never predicts across a boundary (``next_token_targets``).

Run: python packed_example.py            # writes its own dataset
"""

# -- run from a source checkout without installation -------------------------
import os as _os, sys as _sys
_d = _os.path.dirname(_os.path.abspath(__file__))
while _d != _os.path.dirname(_d) and not _os.path.isdir(_os.path.join(_d, 'petastorm_tpu')):
    _d = _os.path.dirname(_d)
if _os.path.isdir(_os.path.join(_d, 'petastorm_tpu')) and _d not in _sys.path:
    _sys.path.insert(0, _d)

import argparse
import functools
import time

import numpy as np
import optax

import jax
import jax.numpy as jnp

from petastorm_tpu import make_reader
from petastorm_tpu.codecs import NdarrayCodec
from petastorm_tpu.etl.dataset_metadata import DatasetWriter
from petastorm_tpu.jax import PackedDataLoader, packing
from petastorm_tpu.models.decoding import generate as lm_generate
from petastorm_tpu.models.transformer import TransformerLM
from petastorm_tpu.unischema import Unischema, UnischemaField

VOCAB = 1024
MAX_LEN = 512
#: one source of truth for the architecture — train() and sample() share it
MODEL_KW = dict(vocab_size=VOCAB, d_model=128, num_heads=4, num_layers=2,
                d_ff=256, max_seq_len=MAX_LEN)

VarTokenSchema = Unischema('VarTokenSchema', [
    UnischemaField('doc_id', np.int64, (), None, False),
    # wildcard first dim: every document has its own length
    UnischemaField('tokens', np.int32, (None,), NdarrayCodec(), False),
])


def generate(url, num_docs=512, seed=0):
    rng = np.random.default_rng(seed)
    with DatasetWriter(url, VarTokenSchema, rows_per_rowgroup=64) as writer:
        for i in range(num_docs):
            length = int(rng.integers(32, MAX_LEN + 1))
            tokens = (rng.zipf(1.4, length) % VOCAB).astype(np.int32)
            writer.write({'doc_id': np.int64(i), 'tokens': tokens})
    return url


def train(dataset_url, steps=20, rows_per_batch=4, lr=3e-3):
    model_kw = MODEL_KW

    def make_step():
        tx = optax.adamw(lr)

        @jax.jit
        def step(params, opt_state, tokens, segment_ids, positions):
            attn = functools.partial(packing.packed_attention,
                                     segment_ids=segment_ids)
            model = TransformerLM(attn_fn=attn, **model_kw)
            targets, weights = packing.next_token_targets(tokens, segment_ids)

            def loss_fn(p):
                # positions restart at 0 per packed document, so each one is
                # embedded as if it began the row
                logits = model.apply(p, tokens,
                                     positions=positions).astype(jnp.float32)
                per_tok = optax.softmax_cross_entropy_with_integer_labels(
                    logits, targets)
                return (per_tok * weights).sum() / jnp.maximum(weights.sum(), 1)

            loss, grads = jax.value_and_grad(loss_fn)(params)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        return step, tx

    step, tx = make_step()
    init_model = TransformerLM(**model_kw)
    params = init_model.init(jax.random.PRNGKey(0),
                             jnp.zeros((1, MAX_LEN), jnp.int32))
    opt_state = tx.init(params)

    done = 0
    stats = {'seen': 0, 'real': 0}

    def count_tokens(batch):
        # Runs on the HOST batch before transfer — stats come for free,
        # no device->host readback against the prefetch pipeline.
        stats['seen'] += batch['segment_ids'].size
        stats['real'] += int((batch['segment_ids'] > 0).sum())
        return batch

    t0 = time.monotonic()
    with make_reader(dataset_url, schema_fields=['tokens'],
                     num_epochs=None, workers_count=4) as reader:
        # PackedDataLoader = pack_stream + the DataLoader's double-buffered
        # device delivery (same prefetch/sharding machinery as images).
        loader = PackedDataLoader(reader, 'tokens', max_len=MAX_LEN,
                                  batch_size=rows_per_batch, prefetch=2,
                                  transform_fn=count_tokens)
        for batch in loader:
            params, opt_state, loss = step(
                params, opt_state, batch['tokens'], batch['segment_ids'],
                batch['positions'])
            done += 1
            if done >= steps:
                break
    loss = float(loss)
    dt = time.monotonic() - t0
    util = stats['real'] / stats['seen']
    print('steps=%d loss=%.3f packing_utilization=%.0f%% tokens/s=%.0f'
          % (done, loss, 100 * util, stats['real'] / dt))
    assert np.isfinite(loss)
    return params, loss, util


def sample(params, prompt_len=8, max_new=16, seed=0):
    """Continue a corpus-style prompt with the compiled KV-cache decoder
    (models.decoding): one batched prefill, then a lax.scan token loop."""
    from petastorm_tpu.ops import flash_attention

    model = TransformerLM(attn_fn=flash_attention, **MODEL_KW)
    params = params.get('params', params)  # train() carries full variables
    rng = np.random.default_rng(seed)
    prompt = jnp.asarray(
        (rng.zipf(1.4, (2, prompt_len)) % VOCAB).astype(np.int32))
    out = lm_generate(model, params, prompt, max_new, temperature=0.8,
                      top_p=0.95, rng=jax.random.PRNGKey(seed))
    for r in range(out.shape[0]):
        print('prompt %s -> %s' % (np.asarray(prompt[r]).tolist(),
                                   np.asarray(out[r]).tolist()))


if __name__ == '__main__':
    from petastorm_tpu.utils import enable_compile_cache, ensure_jax_backend
    ensure_jax_backend()  # applies JAX_PLATFORMS; raises if the backend cannot start
    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--dataset-url', default='file:///tmp/lc_var_tokens')
    parser.add_argument('--steps', type=int, default=20)
    parser.add_argument('--skip-generate', action='store_true')
    parser.add_argument('--sample', action='store_true',
                        help='after training, sample continuations with the '
                             'compiled KV-cache decoder')
    args = parser.parse_args()
    if not args.skip_generate:
        generate(args.dataset_url)
    params, _, _ = train(args.dataset_url, steps=args.steps)
    if args.sample:
        sample(params)
