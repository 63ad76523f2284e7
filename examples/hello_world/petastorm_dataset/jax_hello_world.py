"""Read the hello-world dataset straight into device memory.

The TPU-native analog of the reference's tensorflow/pytorch hello worlds.
"""

# -- run from a source checkout without installation -------------------------
import os as _os, sys as _sys
_d = _os.path.dirname(_os.path.abspath(__file__))
while _d != _os.path.dirname(_d) and not _os.path.isdir(_os.path.join(_d, 'petastorm_tpu')):
    _d = _os.path.dirname(_d)
if _os.path.isdir(_os.path.join(_d, 'petastorm_tpu')) and _d not in _sys.path:
    _sys.path.insert(0, _d)

import argparse

from petastorm_tpu import make_reader
from petastorm_tpu.jax import DataLoader


def jax_hello_world(dataset_url='file:///tmp/hello_world_dataset'):
    # array_4d has a wildcard dim -> keep fixed-shape fields only for batching.
    with make_reader(dataset_url, schema_fields=['id', 'image1']) as reader:
        for batch in DataLoader(reader, batch_size=4):
            print('id:', batch['id'], 'image1:', batch['image1'].shape,
                  'on', next(iter(batch['image1'].devices())))


if __name__ == '__main__':
    from petastorm_tpu.utils import enable_compile_cache, ensure_jax_backend
    ensure_jax_backend()  # applies JAX_PLATFORMS; raises if the backend cannot start
    enable_compile_cache()
    parser = argparse.ArgumentParser(description=__doc__)
    parser.add_argument('--dataset-url', default='file:///tmp/hello_world_dataset')
    args = parser.parse_args()
    jax_hello_world(args.dataset_url)
