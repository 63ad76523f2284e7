"""DataFrame -> training data in two lines (converter example).

The reference's ``examples/spark_dataset_converter`` flow, TPU-native: a
(pandas or Spark) DataFrame is materialized once to cached Parquet and the
converter hands back loaders for JAX, TF, or torch.  With pyspark installed
the same script works on a Spark DataFrame via ``make_spark_converter``.
"""

# -- run from a source checkout without installation -------------------------
import os as _os, sys as _sys
_d = _os.path.dirname(_os.path.abspath(__file__))
while _d != _os.path.dirname(_d) and not _os.path.isdir(_os.path.join(_d, 'petastorm_tpu')):
    _d = _os.path.dirname(_d)
if _os.path.isdir(_os.path.join(_d, 'petastorm_tpu')) and _d not in _sys.path:
    _sys.path.insert(0, _d)

import numpy as np
import pandas as pd

import jax
import jax.numpy as jnp

from petastorm_tpu.spark.spark_dataset_converter import make_pandas_converter


def main():
    rng = np.random.default_rng(0)
    df = pd.DataFrame({
        'features': [rng.standard_normal(16) for _ in range(512)],
        'label': rng.integers(0, 2, 512).astype(np.int64),
    })

    converter = make_pandas_converter(df, parent_cache_dir_url='file:///tmp/converter_cache')
    print('materialized %d rows to %s' % (len(converter), converter.cache_dir_url))

    @jax.jit
    def logreg_loss(w, x, y):
        logits = x @ w
        return jnp.mean(jnp.logaddexp(0.0, logits) - y * logits)

    w = jnp.zeros((16,))
    grad = jax.jit(jax.grad(logreg_loss))
    with converter.make_jax_loader(batch_size=64, num_epochs=2,
                                   workers_count=2) as loader:
        for step, batch in enumerate(loader):
            x = batch['features']  # rectangular list column -> (B, 16) array
            w = w - 0.1 * grad(w, x.astype(jnp.float32), batch['label'].astype(jnp.float32))
            if step % 5 == 0:
                loss = float(logreg_loss(w, x.astype(jnp.float32),
                                         batch['label'].astype(jnp.float32)))
                print('step %d loss %.4f' % (step, loss))

    converter.delete()
    print('cache deleted')


if __name__ == '__main__':
    from petastorm_tpu.utils import enable_compile_cache, ensure_jax_backend
    ensure_jax_backend()  # applies JAX_PLATFORMS; raises if the backend cannot start
    enable_compile_cache()
    main()
