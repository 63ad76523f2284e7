"""Train DLRM on Criteo-shaped Parquet through the columnar loader (config #4).

Uses make_batch_reader (vanilla Parquet, no codecs) -> DataLoader with a
transform assembling (dense, categorical, label) arrays on the host.
"""

# -- run from a source checkout without installation -------------------------
import os as _os, sys as _sys
_d = _os.path.dirname(_os.path.abspath(__file__))
while _d != _os.path.dirname(_d) and not _os.path.isdir(_os.path.join(_d, 'petastorm_tpu')):
    _d = _os.path.dirname(_d)
if _os.path.isdir(_os.path.join(_d, 'petastorm_tpu')) and _d not in _sys.path:
    _sys.path.insert(0, _d)

import argparse
import time

import numpy as np
import optax

import jax
import jax.numpy as jnp

from petastorm_tpu import make_batch_reader
from petastorm_tpu.benchmark import StallMonitor
from petastorm_tpu.jax import DataLoader
from petastorm_tpu.models.dlrm import DLRM

from generate_criteo_parquet import NUM_CATEGORICAL, NUM_DENSE, VOCAB_SIZES


def pack_columns(batch):
    dense = np.stack([batch['dense_%d' % i] for i in range(NUM_DENSE)], axis=1)
    cats = np.stack([batch['cat_%d' % i] for i in range(NUM_CATEGORICAL)], axis=1)
    return {'dense': np.log1p(dense).astype(np.float32), 'cats': cats,
            'label': batch['label'].astype(np.float32)}


def train(dataset_url, epochs=1, batch_size=2048, lr=1e-3, scan_steps=0):
    model = DLRM(vocab_sizes=VOCAB_SIZES)
    params = model.init(jax.random.PRNGKey(0),
                       jnp.zeros((1, NUM_DENSE)), jnp.zeros((1, NUM_CATEGORICAL), jnp.int32))
    tx = optax.adagrad(lr)
    opt_state = tx.init(params)

    @jax.jit
    def train_step(params, opt_state, batch):
        def loss_fn(p):
            logits = model.apply(p, batch['dense'], batch['cats'])
            return optax.sigmoid_binary_cross_entropy(logits, batch['label']).mean()

        loss, grads = jax.value_and_grad(loss_fn)(params)
        updates, opt_state2 = tx.update(grads, opt_state)
        return optax.apply_updates(params, updates), opt_state2, loss

    monitor = StallMonitor()
    for epoch in range(epochs):
        losses = []
        t0 = time.monotonic()
        with make_batch_reader(dataset_url, num_epochs=1, workers_count=4) as reader:
            loader = DataLoader(reader, batch_size=batch_size, transform_fn=pack_columns)
            if scan_steps >= 1:
                # Fused consumption (scan_batches): the DLRM step is tiny
                # (embedding gathers + small MLPs), so per-step dispatch
                # latency — not compute — is where a fast device stalls;
                # k steps per stacked device_put + lax.scan dispatch
                # amortizes it k-fold.
                def scan_step(carry, batch):
                    p, o = carry
                    p, o, loss = train_step(p, o, batch)
                    return (p, o), loss
                for (params, opt_state), outs in loader.scan_batches(
                        scan_step, (params, opt_state),
                        steps_per_call=scan_steps, donate_carry=False):
                    losses.extend(np.asarray(outs).ravel().tolist())
            else:
                for batch in monitor.wrap(loader):
                    params, opt_state, loss = train_step(params, opt_state, batch)
                    losses.append(float(loss))
        stall = ('(fused scan: per-step stall n/a)' if scan_steps >= 1
                 else monitor.report())
        print('epoch %d: loss=%.4f (%.1fs) stall=%s'
              % (epoch, np.mean(losses[-10:]), time.monotonic() - t0, stall))
    return np.mean(losses[-10:])


if __name__ == '__main__':
    from petastorm_tpu.utils import enable_compile_cache, ensure_jax_backend
    ensure_jax_backend()  # applies JAX_PLATFORMS; raises if the backend cannot start
    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--dataset-url', default='file:///tmp/criteo_parquet')
    parser.add_argument('--epochs', type=int, default=2)
    parser.add_argument('--batch-size', type=int, default=2048)
    parser.add_argument('--scan-steps', type=int, default=0,
                        help='consume via scan_batches: K steps per stacked '
                             'device_put + lax.scan dispatch — use when '
                             'dispatch latency, not compute, is the stall '
                             '(tiny DLRM steps on fast devices)')
    args = parser.parse_args()
    train(args.dataset_url, args.epochs, args.batch_size,
          scan_steps=args.scan_steps)
