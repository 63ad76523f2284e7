"""Train a flax MLP on the MNIST Parquet dataset through the TPU-native loader.

The end-to-end acceptance flow (BASELINE.json config #1): make_reader ->
petastorm_tpu.jax.DataLoader -> jitted train step.  No reference equivalent
exists for JAX; the structure mirrors ``examples/mnist/pytorch_example.py``.
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

from petastorm_tpu import make_reader
from petastorm_tpu.jax import DataLoader
from petastorm_tpu.models.mlp import MLP


def train(dataset_url, epochs=3, batch_size=128, lr=1e-3,
          checkpoint_dir=None, save_every=100):
    model = MLP()
    params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 28, 28)))['params']
    tx = optax.adam(lr)
    opt_state = tx.init(params)

    # --checkpoint-dir: the full train-state story (TrainStateManager) —
    # params ride as the orbax pytree; the optimizer state and the
    # loader's EXACT mid-epoch token ride as the data-plane blob, so a
    # restart resumes the stream at the batch it left (nothing re-read,
    # nothing skipped) with adam moments intact.
    mgr = None
    start_epoch, loader_token, global_step = 0, None, 0
    if checkpoint_dir:
        from petastorm_tpu.checkpoint import TrainStateManager
        mgr = TrainStateManager(checkpoint_dir, save_interval_steps=save_every,
                                max_to_keep=2)
        step, model_state, data_state = mgr.restore_latest()
        if step is not None:
            params = model_state['params']
            opt_state = jax.tree_util.tree_map(jnp.asarray, data_state['opt'])
            start_epoch, loader_token = data_state['epoch'], data_state['loader']
            global_step = step + 1
            print('resumed at step %d (epoch %d, mid-epoch token: %s)'
                  % (step, start_epoch, loader_token is not None))

    @jax.jit
    def train_step(params, opt_state, images, labels):
        def loss_fn(p):
            logits = model.apply({'params': p}, images)
            loss = optax.softmax_cross_entropy_with_integer_labels(logits, labels).mean()
            return loss, logits

        (loss, logits), grads = jax.value_and_grad(loss_fn, has_aux=True)(params)
        updates, opt_state2 = tx.update(grads, opt_state)
        params2 = optax.apply_updates(params, updates)
        acc = jnp.mean(jnp.argmax(logits, -1) == labels)
        return params2, opt_state2, loss, acc

    if start_epoch >= epochs:
        print('checkpoint already covers all %d epochs — nothing to train'
              % epochs)
        if mgr is not None:
            mgr.close()
        return float('nan')

    for epoch in range(start_epoch, epochs):
        t0 = time.monotonic()
        losses, accs, rows = [], [], 0
        resume = loader_token if epoch == start_epoch else None
        loader_token = None  # consumed: later epochs start fresh
        with make_reader(dataset_url, num_epochs=1, workers_count=4,
                         resume_state=(resume or {}).get('reader')) as reader:
            loader = DataLoader(reader, batch_size=batch_size,
                                shuffling_queue_capacity=2048, seed=epoch,
                                resume_state=resume)
            for batch in loader:
                params, opt_state, loss, acc = train_step(
                    params, opt_state, batch['image'], batch['digit'])
                losses.append(float(loss)); accs.append(float(acc))
                rows += batch_size
                if mgr is not None and mgr.should_save(global_step):
                    mgr.save(global_step, {'params': params},
                             data_state={'epoch': epoch,
                                         'opt': jax.device_get(opt_state),
                                         'loader': loader.state_dict()})
                global_step += 1
        dt = time.monotonic() - t0
        if losses:
            print('epoch %d: loss=%.4f acc=%.3f (%.0f rows/s)'
                  % (epoch, np.mean(losses), np.mean(accs[-20:]), rows / dt))
        else:
            # a resume token taken at the stream's end yields no batches:
            # the epoch was already complete
            print('epoch %d: already complete at resume' % epoch)
    if mgr is not None:
        mgr.save(global_step, {'params': params},
                 data_state={'epoch': epochs, 'opt': jax.device_get(opt_state),
                             'loader': None}, force=True)
        mgr.wait_until_finished()
        mgr.close()
    return float(np.mean(accs[-20:])) if accs else float('nan')


if __name__ == '__main__':
    from petastorm_tpu.utils import enable_compile_cache, ensure_jax_backend
    ensure_jax_backend()  # applies JAX_PLATFORMS; raises if the backend cannot start
    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--dataset-url', default='file:///tmp/mnist_petastorm')
    parser.add_argument('--epochs', type=int, default=3)
    parser.add_argument('--batch-size', type=int, default=128)
    parser.add_argument('--checkpoint-dir', default=None,
                        help='enable TrainStateManager checkpointing: '
                             'params + optimizer state + the loader\'s '
                             'exact mid-epoch token every '
                             '--save-every steps; rerun with the same dir '
                             'to resume at the batch the last save saw')
    parser.add_argument('--save-every', type=int, default=100)
    args = parser.parse_args()
    final_acc = train(args.dataset_url, args.epochs, args.batch_size,
                      checkpoint_dir=args.checkpoint_dir,
                      save_every=args.save_every)
    print('final accuracy: %.3f' % final_acc)
