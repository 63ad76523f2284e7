"""NGram temporal reader over AV-sensor-like Parquet (acceptance config #5).

Generates a multi-field timestamped dataset, reads sliding windows with
delta-threshold gap filtering, and feeds window tensors to a jitted step.
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

import jax
import jax.numpy as jnp

from petastorm_tpu import make_reader
from petastorm_tpu.codecs import NdarrayCodec
from petastorm_tpu.etl.dataset_metadata import DatasetWriter
from petastorm_tpu.jax import DataLoader
from petastorm_tpu.ngram import NGram
from petastorm_tpu.unischema import Unischema, UnischemaField

SensorSchema = Unischema('SensorSchema', [
    UnischemaField('timestamp', np.int64, (), None, False),
    UnischemaField('lidar', np.float32, (32,), NdarrayCodec(), False),
    UnischemaField('velocity', np.float32, (3,), NdarrayCodec(), False),
])


def generate(url, rows=600, seed=0):
    rng = np.random.default_rng(seed)
    t = 0
    def row_gen():
        nonlocal t
        for i in range(rows):
            t += int(rng.integers(1, 3)) if i % 50 else 100  # dropouts every 50
            yield {'timestamp': np.int64(t),
                   'lidar': rng.standard_normal(32).astype(np.float32),
                   'velocity': rng.standard_normal(3).astype(np.float32)}
    with DatasetWriter(url, SensorSchema, rows_per_rowgroup=100) as w:
        w.write_many(row_gen())


def main(url):
    generate(url)
    ngram = NGram(fields={-2: ['lidar'], -1: ['lidar'], 0: ['lidar', 'velocity']},
                  delta_threshold=10, timestamp_field='timestamp')

    @jax.jit
    def predict_speed(history, velocity):
        return jnp.mean(history, axis=(1, 2)) + jnp.linalg.norm(velocity, axis=1)

    def collate(batch):
        history = np.stack([batch[-2]['lidar'], batch[-1]['lidar']], axis=1)
        return {'history': history, 'velocity': batch[0]['velocity']}

    with make_reader(url, schema_fields=ngram, shuffle_row_groups=False) as reader:
        loader = DataLoader(reader, batch_size=32, transform_fn=collate)
        for i, batch in enumerate(loader):
            out = predict_speed(batch['history'], batch['velocity'])
            if i == 0:
                print('window batch: history', batch['history'].shape,
                      'velocity', batch['velocity'].shape, '->', out.shape)
    print('done')


if __name__ == '__main__':
    from petastorm_tpu.utils import enable_compile_cache, ensure_jax_backend
    ensure_jax_backend()  # applies JAX_PLATFORMS; raises if the backend cannot start
    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--dataset-url', default='file:///tmp/ngram_sensor')
    args = parser.parse_args()
    main(args.dataset_url)
