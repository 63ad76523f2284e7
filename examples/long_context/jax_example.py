"""Long-context LM training: token Parquet -> ring-attention Transformer.

The sequence-parallel showcase: documents land in Parquet as token arrays
(NdarrayCodec), the reader streams them columnar, and the model shards the
sequence axis over the device mesh — ring attention rotates K/V blocks over
ICI so no device ever holds the full sequence.  On a single device the same
script runs with the Pallas flash kernel instead (``--strategy flash``).

Run: python generate_token_parquet.py /tmp/lc_tokens
     python jax_example.py --dataset-url file:///tmp/lc_tokens
"""

# -- run from a source checkout without installation -------------------------
import os as _os, sys as _sys
_d = _os.path.dirname(_os.path.abspath(__file__))
while _d != _os.path.dirname(_d) and not _os.path.isdir(_os.path.join(_d, 'petastorm_tpu')):
    _d = _os.path.dirname(_d)
if _os.path.isdir(_os.path.join(_d, 'petastorm_tpu')) and _d not in _sys.path:
    _sys.path.insert(0, _d)

import argparse

import numpy as np
import optax

import jax
import jax.numpy as jnp

from petastorm_tpu import make_reader
from petastorm_tpu.jax import DataLoader
from petastorm_tpu.models.transformer import (TransformerLM, make_attn_fn,
                                              param_shardings)
from petastorm_tpu.parallel import make_mesh, global_batch_from_local

from generate_token_parquet import SEQ_LEN, VOCAB


def main():
    parser = argparse.ArgumentParser()
    parser.add_argument('--dataset-url', default='file:///tmp/lc_tokens')
    parser.add_argument('--strategy', default='auto',
                        choices=['auto', 'flash', 'ring', 'ulysses', 'dense'])
    parser.add_argument('--batch-size', type=int, default=8)
    parser.add_argument('--steps', type=int, default=30)
    parser.add_argument('--block-k', type=int, default=None,
                        help='chunk ring-attention score tiles (memory cap '
                             'for very long local sequences)')
    args = parser.parse_args()

    n_dev = len(jax.devices())
    strategy = args.strategy
    if strategy == 'auto':
        strategy = 'ring' if n_dev > 1 else 'flash'
    if args.block_k is not None and strategy != 'ring':
        parser.error('--block-k only applies to the ring strategy '
                     '(resolved strategy: %s)' % strategy)

    if strategy in ('ring', 'ulysses'):
        sp = 2 if n_dev % 2 == 0 else 1
        mesh = make_mesh({'data': n_dev // sp, 'seq': sp})
    else:
        mesh = make_mesh({'data': n_dev, 'seq': 1})
    from jax.sharding import NamedSharding, PartitionSpec as P
    batch_sharding = NamedSharding(mesh, P('data', 'seq'))

    # The global batch must divide the 'data' mesh axis; round the requested
    # size up to the nearest multiple.
    data_size = mesh.shape['data']
    batch_size = -(-args.batch_size // data_size) * data_size
    if batch_size != args.batch_size:
        print('batch size %d -> %d (multiple of data axis %d)'
              % (args.batch_size, batch_size, data_size))

    model = TransformerLM(
        vocab_size=VOCAB, d_model=256, num_heads=8, num_layers=4, d_ff=1024,
        max_seq_len=SEQ_LEN, attn_fn=make_attn_fn(mesh, strategy, head_axis=None,
                                             block_k=args.block_k),
        remat=True)
    rng = jax.random.PRNGKey(0)
    init_tokens = jnp.zeros((mesh.shape['data'], SEQ_LEN), jnp.int32)
    params = model.init(rng, init_tokens)['params']
    params = jax.device_put(params, param_shardings(params, mesh))
    tx = optax.adamw(3e-4)
    opt_state = tx.init(params)

    @jax.jit
    def train_step(params, opt_state, tokens):
        def loss_fn(p):
            logits = model.apply({'params': p}, tokens)
            labels = jnp.roll(tokens, -1, axis=1)
            return optax.softmax_cross_entropy_with_integer_labels(
                logits, labels).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, new_opt = tx.update(grads, opt_state, params)
        return optax.apply_updates(params, updates), new_opt, loss

    step = 0
    with make_reader(args.dataset_url, num_epochs=None, columnar_decode=True,
                     workers_count=4) as reader:
        loader = DataLoader(reader, batch_size=batch_size, prefetch=2,
                            drop_last=True)
        for batch in loader:
            tokens = global_batch_from_local(
                np.ascontiguousarray(batch['tokens']), batch_sharding)
            params, opt_state, loss = train_step(params, opt_state, tokens)
            step += 1
            if step % 10 == 0:
                print('step %d  loss %.4f  (%s, %d devices)'
                      % (step, float(loss), strategy, n_dev))
            if step >= args.steps:
                break
    print('done: %d steps of seq_len=%d with %s attention' % (step, SEQ_LEN, strategy))


if __name__ == '__main__':
    from petastorm_tpu.utils import enable_compile_cache, ensure_jax_backend
    ensure_jax_backend()  # applies JAX_PLATFORMS; raises if the backend cannot start
    enable_compile_cache()
    main()
