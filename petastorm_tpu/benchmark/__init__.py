"""Measurement harnesses: reader throughput, training data-stall profiling,
and the bottleneck advisor."""

from petastorm_tpu.benchmark.advisor import diagnose, format_report  # noqa: F401
from petastorm_tpu.benchmark.stall_profiler import StallMonitor  # noqa: F401
from petastorm_tpu.benchmark.throughput import BenchmarkResult, reader_throughput  # noqa: F401
from petastorm_tpu.benchmark.autotune import autotune  # noqa: F401
from petastorm_tpu.benchmark.trace import TraceRecorder  # noqa: F401
