"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding logic
(mesh construction, per-host batch assembly) is exercised without TPU
hardware.  Must run before any test imports jax.
"""

import faulthandler
import json
import os
import threading
import time

# The suite has died natively before (PR 1: an mmap-backed ParquetFile
# closed mid-read segfaulted teardown): faulthandler turns a native
# crash into a stack dump.  (pytest's builtin faulthandler plugin
# re-enables this onto a dup of the REAL stderr at configure time; this
# call covers any pre-configure crash window and non-pytest imports.)
faulthandler.enable()

_flags = os.environ.get('XLA_FLAGS', '')
if '--xla_force_host_platform_device_count' not in _flags:
    os.environ['XLA_FLAGS'] = (_flags + ' --xla_force_host_platform_device_count=8').strip()
os.environ['JAX_PLATFORMS'] = 'cpu'

# Runtime lockdep (ISSUE 11): armed for the whole suite, so every tier-1
# run doubles as a deadlock-detection run — the utils.locks factory
# returns order-tracking wrappers and any lock-order inversion lands in
# the watchdog/telemetry artifact below.  Must be set BEFORE any
# petastorm_tpu module import (module-level locks are constructed at
# import time).  setdefault: an explicit =0 disarms locally.
os.environ.setdefault('PETASTORM_TPU_LOCKDEP', '1')

import jax  # noqa: E402

jax.config.update('jax_platforms', 'cpu')

import numpy as np  # noqa: E402
import pytest  # noqa: E402


@pytest.hookimpl(trylast=True)  # after builtin plugins have stashed fds
def pytest_configure(config):
    # pytest-timeout is not installed; register the mark so the suite
    # stays warning-free (the marks document intent either way).
    config.addinivalue_line('markers', 'timeout(seconds): per-test time budget')
    config.addinivalue_line('markers', 'slow: long-running correctness test')
    # Suite-level hang watchdog: the tier-1 run is killed at a hard 870s
    # budget on some hosts, historically with NO python traceback.  The
    # 800s repeating timer dumps every thread's stack just before that
    # external kill (exit=False: diagnose, don't interfere).  It must
    # write to the REAL stderr: pytest's fd-capture replaces fd 2 before
    # conftest import, so a naive dump_traceback_later() lands in a
    # per-test capture buffer that dies, unread, with the killed process
    # (verified on this box) — reuse the original-stderr dup the builtin
    # faulthandler plugin stashed at configure time.  The timeout knob
    # exists so tests can pin the watchdog end-to-end without an 800s
    # wait.  NOTE: do not also set the `faulthandler_timeout` ini option
    # — its per-test timers share the single global faulthandler timer
    # and would cancel this one at the first test.
    timeout_s = float(os.environ.get('PETASTORM_TPU_FAULT_TIMEOUT', 800))
    kwargs = {}
    try:
        from _pytest.faulthandler import fault_handler_stderr_fd_key
        kwargs['file'] = config.stash[fault_handler_stderr_fd_key]
    except Exception:  # plugin layout changed: an fd-2 dump beats none
        pass
    faulthandler.dump_traceback_later(timeout=timeout_s, repeat=True,
                                      exit=False, **kwargs)
    # Telemetry crash artifact (ISSUE 5 satellite): when the watchdog
    # window elapses (suite hung — the external kill follows shortly), a
    # companion timer writes every live registry snapshot + trace-recorder
    # timeline to the artifact path CI uploads on failure, so the next
    # silent-death bug ships with a timeline attached, not just thread
    # stacks.  faulthandler can only dump stacks (C-level timer); this
    # python-level dump needs its own timer.  The telemetry module is
    # imported HERE, on the main thread: a first import of native
    # extension modules from the timer thread (concurrent with the
    # faulthandler dump) has segfaulted the child on this host.
    global _TELEMETRY, _TELEMETRY_TIMER, _LOCKDEP
    try:
        # Lockdep runtime pre-import (ISSUE 11): the dump below runs on
        # a timer thread, which must NEVER be the first importer of
        # anything (see the telemetry import note) — bind the module
        # here on the main thread.
        from petastorm_tpu.analysis.lockdep import runtime as _LOCKDEP
    except Exception:
        _LOCKDEP = None
    try:
        from petastorm_tpu import telemetry as _TELEMETRY
        # dump_state's own lazy imports (benchmark.trace and through it
        # the petastorm_tpu package tree) must also happen NOW: the
        # timer thread must never be the first importer of anything.
        _TELEMETRY.dump_state()
        # Always-on flight recorder (ISSUE 7): the suite process keeps a
        # bounded ring of periodic registry frames, so the watchdog
        # artifact carries the minutes BEFORE a hang, not just the final
        # counter totals.  Armed here on the main thread (the tick
        # thread is import-free by construction).
        _TELEMETRY.flight.enable(label='pytest')
    except Exception:  # no telemetry -> no dump, never a broken suite
        _TELEMETRY = None
    if _TELEMETRY is not None:
        _arm_telemetry_timer(timeout_s)


_TELEMETRY = None
_TELEMETRY_TIMER = None
_LOCKDEP = None


def _arm_telemetry_timer(delay_s):
    """Self-re-arming dump timer: after the first (watchdog-window) fire
    it re-dumps every 30s, overwriting the artifact — like faulthandler's
    repeat=True, so a hang that BEGINS after the first window is still
    captured by the last dump before the external kill (the single-shot
    version shipped a healthy pre-hang snapshot)."""
    global _TELEMETRY_TIMER

    def fire():
        _write_telemetry_dump('watchdog_timeout')
        _arm_telemetry_timer(30.0)

    _TELEMETRY_TIMER = threading.Timer(delay_s, fire)
    _TELEMETRY_TIMER.daemon = True
    _TELEMETRY_TIMER.start()


def _telemetry_dump_path():
    return os.environ.get(
        'PETASTORM_TPU_TELEMETRY_ARTIFACT',
        os.path.join(os.path.dirname(os.path.abspath(__file__)), '..',
                     'test-artifacts', 'telemetry_dump.json'))


def _write_telemetry_dump(reason):
    """Best-effort: a failing diagnostics write must never fail (or hang)
    the suite it is diagnosing.  Import-free by design (see
    pytest_configure) — this may run on a timer thread mid-crash."""
    if _TELEMETRY is None:
        return
    try:
        state = _TELEMETRY.dump_state()
        state['reason'] = reason
        state['unix_time'] = time.time()
        if _LOCKDEP is not None:
            # Lockdep dump (ISSUE 11): the observed lock-order graph,
            # acquisition-stack witnesses, and any order inversions ride
            # the same artifact — a hung suite ships its deadlock
            # evidence, not just thread stacks.
            state['lockdep'] = _LOCKDEP.state_dict()
        path = _telemetry_dump_path()
        os.makedirs(os.path.dirname(path) or '.', exist_ok=True)
        with open(path, 'w') as f:
            json.dump(state, f, default=str)
        # The flight ring also lands as its own artifact next to the
        # dump (ISSUE 7): `petastorm-tpu-diagnose --flight` reads it
        # directly, and CI's failure upload ships the whole directory.
        recorder = _TELEMETRY.flight.get()
        if recorder is not None:
            recorder.persist(
                path=os.path.join(os.path.dirname(path),
                                  'flight_recorder.json'),
                reason=reason)
    except Exception as e:  # noqa: BLE001
        print('telemetry dump failed: %s' % (e,))


def pytest_sessionfinish(session, exitstatus):
    if _TELEMETRY_TIMER is not None:
        _TELEMETRY_TIMER.cancel()
    # 0 = green, 5 = no tests collected; anything else failed/errored —
    # leave the registry+timeline state next to the junit output.
    if exitstatus not in (0, 5):
        _write_telemetry_dump('exitstatus_%s' % (exitstatus,))


@pytest.fixture(scope='session')
def rng():
    return np.random.default_rng(42)


@pytest.fixture(params=[False, True], ids=['inline', 'pumped'])
def transfer(request):
    """The loader's two iteration paths, as its ``transfer=`` option: off
    (``_iter_inline``, what ``'auto'`` resolves to on the CPU backend) and
    on (``_iter_pumped``: dispatch thread and transfer plane, the path every
    streaming cell of the benchmark runs on the chip)."""
    return request.param
