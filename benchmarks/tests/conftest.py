import os
import sys

os.environ.setdefault('JAX_PLATFORMS', 'cpu')
BENCHMARKS = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for path in (BENCHMARKS, os.path.dirname(BENCHMARKS)):
    if path not in sys.path:
        sys.path.insert(0, path)
