"""Direct unit tests for ``test_util.emulation.BandwidthLimitedFilesystem``
(ISSUE 14 satellite): it is the correctness harness of the ingest plane's
and the adaptive scheduler's tests, so its cold-latency gate and bandwidth
accounting are pinned here.

Sleeps are intercepted (monkeypatched ``time.sleep`` in the emulation
module), so the tests are deterministic and instant.
"""

import io

import pytest

from petastorm_tpu.test_util import BandwidthLimitedFilesystem
from petastorm_tpu.test_util import emulation


class _FakeFs(object):
    """In-memory inner fs: one blob per path, sizes reported exactly."""

    def __init__(self, files):
        self._files = dict(files)

    def open(self, path, mode='rb', **kwargs):
        if 'r' in mode and 'b' in mode:
            return io.BytesIO(self._files[path])
        return io.BytesIO()

    def size(self, path):
        return len(self._files[path])


@pytest.fixture()
def sleeps(monkeypatch):
    recorded = []
    monkeypatch.setattr(emulation.time, 'sleep', recorded.append)
    return recorded


def test_bandwidth_accounting_is_per_chunk(sleeps):
    blob = bytes(600 * 1024)   # 600 KiB -> 3 chunks at the 256 KiB stride
    fs = BandwidthLimitedFilesystem(_FakeFs({'/a': blob}), bps=1e6)
    with fs.open('/a') as handle:
        out = handle.read()
    assert out == blob
    # one sleep per streamed chunk, each chunk's share of bytes/bps,
    # summing to exactly total_bytes/bps
    assert len(sleeps) == 3
    assert sleeps[0] == emulation._BW_CHUNK / 1e6
    assert sum(sleeps) == pytest.approx(len(blob) / 1e6)


def test_bounded_read_pays_only_its_bytes(sleeps):
    blob = bytes(512 * 1024)
    fs = BandwidthLimitedFilesystem(_FakeFs({'/a': blob}), bps=1e6)
    handle = fs.open('/a')
    assert len(handle.read(100)) == 100
    assert sum(sleeps) == pytest.approx(100 / 1e6)


def test_cold_latency_gate_by_size(sleeps):
    files = {'/big': bytes(2 << 20), '/small': bytes(1024)}
    fs = BandwidthLimitedFilesystem(_FakeFs(files), bps=1e9,
                                    cold_latency=1.2)
    # big file (>= the 1 MiB default threshold): the FIRST read pays the
    # cold GET, before any bandwidth sleep
    handle = fs.open('/big')
    handle.read(10)
    assert sleeps[0] == 1.2
    # ...and only once per handle
    sleeps.clear()
    handle.read(10)
    assert 1.2 not in sleeps
    # a fresh handle of the same file pays it again (per-GET semantics)
    sleeps.clear()
    fs.open('/big').read(10)
    assert sleeps[0] == 1.2
    # small files never pay it
    sleeps.clear()
    fs.open('/small').read(10)
    assert 1.2 not in sleeps


def test_cold_latency_zero_disables_size_probe(sleeps):
    class _NoSizeFs(_FakeFs):
        def size(self, path):
            raise AssertionError('size() must not be called')

    fs = BandwidthLimitedFilesystem(_NoSizeFs({'/a': bytes(2 << 20)}),
                                    bps=1e9)
    fs.open('/a').read(10)   # no cold_latency -> no size probe, no gate


def test_non_binary_modes_pass_through(sleeps):
    fs = BandwidthLimitedFilesystem(_FakeFs({'/a': b'x'}), bps=1.0,
                                    cold_latency=9.0)
    handle = fs.open('/a', 'wb')
    assert not sleeps   # write handles are never throttled
    handle.close()
