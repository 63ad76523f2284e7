"""Closed-loop fleet autoscaler (ISSUE 16).

The dispatcher already measures everything an autoscaler needs — the
health engine's windowed regimes say when leases starve (not enough
decode workers) and when the fleet idles (too many), and PR 15's drain
path makes scale-in safe.  This module closes the loop: an in-dispatcher
tick controller (the flight-recorder pattern — ``maybe_tick()`` from the
serve loop, NO new control-plane thread) computes a target worker count
and acts through a pluggable :class:`WorkerLauncher` seam.

Control law (deliberately boring — an exciting autoscaler is a flapping
one):

* **scale out** when pending splits have starved for
  ``autoscale_starve_s`` — no alive worker has a free lease slot (or
  none are alive at all) while work waits;
* **scale in** when the fleet has been fully idle (no pending, no
  leased) for ``autoscale_idle_s`` with more than ``autoscale_min_workers``
  alive — via the graceful drain path, choosing the worker whose
  departure costs the least cache-directory coverage;
* **damping**: a cooldown after ANY action, at most ``autoscale_step``
  workers per action, and the alive count clamped to
  ``[autoscale_min_workers, autoscale_max_workers]``.  The chaos
  scale-storm scenarios assert the action count stays within the bound
  these knobs imply.

Kill switch: ``PETASTORM_TPU_NO_AUTOSCALE=1`` beats any config — the
controller constructs but never acts (the doctor probe reports the
state).
"""

import logging
import os
import subprocess
import sys
import time

from petastorm_tpu.telemetry import decisions as _decisions

logger = logging.getLogger(__name__)

__all__ = ['KILL_SWITCH', 'killed', 'WorkerLauncher',
           'SubprocessWorkerLauncher', 'Autoscaler']

KILL_SWITCH = 'PETASTORM_TPU_NO_AUTOSCALE'


def killed():
    """True when the environment vetoes autoscaling on this host."""
    return os.environ.get(KILL_SWITCH, '') not in ('', '0')


class WorkerLauncher(object):
    """The seam between the control law and real worker processes.

    The dispatcher never spawns processes itself: scale-out calls
    ``spawn(dispatcher_addr)``, scale-in is executed by the dispatcher's
    own drain path and reported here via ``notify_drain(worker_id)`` so
    a launcher can reap the matching child.  Tests substitute a fake
    that records both call streams.
    """

    def spawn(self, dispatcher_addr):
        raise NotImplementedError

    def notify_drain(self, worker_id):
        """A drain was initiated on ``worker_id`` (informational)."""

    def close(self):
        """Release launcher resources (kill children it still owns)."""


class SubprocessWorkerLauncher(WorkerLauncher):
    """Launch real decode workers as child processes of the dispatcher.

    Children run the same entry the operator would
    (``petastorm-tpu-data-service worker --dispatcher ...``) with the
    SIGTERM-drain handler installed, so a dispatcher shutdown or an
    explicit drain terminates them gracefully.
    """

    def __init__(self, worker_args=None):
        self._worker_args = list(worker_args or ())
        self._procs = []

    def spawn(self, dispatcher_addr):
        cmd = [sys.executable, '-m', 'petastorm_tpu.service.cli',
               'worker', '--dispatcher', dispatcher_addr]
        cmd += self._worker_args
        # The child resolves ``-m petastorm_tpu...`` via sys.path, which
        # for ``-m`` starts at the child's cwd — prepend the package
        # root so a dispatcher launched from anywhere spawns importable
        # workers.
        root = os.path.dirname(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
        env = dict(os.environ)
        env['PYTHONPATH'] = root + (
            os.pathsep + env['PYTHONPATH'] if env.get('PYTHONPATH') else '')
        # Decode workers are host-only: a chip belongs to one process (the
        # trainer) — same pin as workers_pool/exec_in_new_process.py.
        env['JAX_PLATFORMS'] = 'cpu'
        proc = subprocess.Popen(cmd, env=env)
        self._procs.append(proc)
        logger.info('autoscaler spawned worker pid %d', proc.pid)
        return proc.pid

    def close(self):
        for proc in self._procs:
            if proc.poll() is None:
                proc.terminate()
        deadline = time.monotonic() + 10.0
        for proc in self._procs:
            try:
                proc.wait(max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                proc.kill()
                proc.wait(5.0)
        self._procs = []


class Autoscaler(object):
    """The tick controller.  Owned and called by the dispatcher thread
    (serve-loop ticks), so it needs no lock of its own; every method
    runs under the dispatcher's sequencing.
    """

    #: Seconds between observation ticks (the serve loop polls at
    #: ~100 ms; sub-second control would just chase noise).
    TICK_S = 1.0

    def __init__(self, config, launcher, now=None):
        self.config = config
        self.launcher = launcher
        self.enabled = bool(config.autoscale) and not killed()
        now = time.monotonic() if now is None else now
        self._next_tick = now
        self._cooldown_until = 0.0
        self._starve_since = None
        self._idle_since = None
        # Action counters — the chaos scale-storm bound and the stats
        # rollup read these.
        self.scale_outs = 0
        self.scale_ins = 0
        self.suppressed = 0   # wanted to act; cooldown/bounds said no
        self.last_action = None
        self.last_action_t = None
        # Decision journal (ISSUE 20): the dispatcher points this at its
        # ledger-persisted journal so every action/suppression explains
        # itself; None falls through to the process journal.
        self.decisions = None

    @property
    def actions(self):
        return self.scale_outs + self.scale_ins

    def maybe_tick(self, observation, now=None):
        """One control-law evaluation; returns the action taken.

        ``observation`` is the dispatcher's view under its lock::

            {'pending': int, 'leased': int,
             'alive': [worker_id, ...],        # non-draining, fresh hb
             'free_slots': int,                # alive workers w/o lease
             'coverage': {worker_id: int}}     # cache digests held

        Returns ``None`` (no-op), ``('scale_out', n)`` after spawning
        ``n`` workers, or ``('scale_in', worker_id)`` naming the drain
        victim — the DISPATCHER executes the drain (it owns that path).
        """
        now = time.monotonic() if now is None else now
        if not self.enabled or now < self._next_tick:
            return None
        self._next_tick = now + self.TICK_S
        pending = int(observation.get('pending', 0))
        leased = int(observation.get('leased', 0))
        alive = list(observation.get('alive') or ())
        free_slots = int(observation.get('free_slots', 0))

        starved = pending > 0 and (not alive or free_slots == 0)
        idle = pending == 0 and leased == 0 and alive
        # Explicit None checks: a start stamp of 0.0 (injected clocks in
        # tests/doctor) is falsy but set.
        if starved:
            if self._starve_since is None:
                self._starve_since = now
        else:
            self._starve_since = None
        if idle:
            if self._idle_since is None:
                self._idle_since = now
        else:
            self._idle_since = None

        cfg = self.config
        cooldown_left = max(0.0, self._cooldown_until - now)
        if starved and now - self._starve_since >= cfg.autoscale_starve_s:
            want = min(cfg.autoscale_step,
                       cfg.autoscale_max_workers - len(alive))
            inputs = {'pending': pending, 'leased': leased, 'alive': alive,
                      'free_slots': free_slots,
                      'starve_s': round(now - self._starve_since, 3),
                      'threshold_s': cfg.autoscale_starve_s,
                      'step': cfg.autoscale_step,
                      'max_workers': cfg.autoscale_max_workers,
                      'cooldown_remaining_s': round(cooldown_left, 3)}
            if want <= 0 or now < self._cooldown_until:
                self.suppressed += 1
                _decisions.record_decision(
                    'autoscaler', 'hold', 'autoscale_cooldown_s',
                    dict(inputs, want=want, wanted='scale_out'),
                    suppressed=True, cooldown_until=self._cooldown_until,
                    journal=self.decisions)
                return None
            spawned = 0
            for _ in range(want):
                try:
                    self.launcher.spawn(observation['dispatcher_addr'])
                    spawned += 1
                except Exception:  # noqa: BLE001 — a dead launcher must
                    # not take the serve loop down; starvation persists
                    # and the next tick (post-cooldown) retries.
                    logger.exception('autoscaler spawn failed')
                    break
            if not spawned:
                return None
            self.scale_outs += 1
            self._after_action('scale_out', now)
            self._starve_since = None
            _decisions.record_decision(
                'autoscaler', 'scale_out', 'autoscale_starve_s', inputs,
                cooldown_until=self._cooldown_until, spawned=spawned,
                journal=self.decisions)
            return ('scale_out', spawned)

        if idle and now - self._idle_since >= cfg.autoscale_idle_s \
                and len(alive) > cfg.autoscale_min_workers:
            coverage = dict(observation.get('coverage') or {})
            inputs = {'pending': pending, 'leased': leased, 'alive': alive,
                      'idle_s': round(now - self._idle_since, 3),
                      'threshold_s': cfg.autoscale_idle_s,
                      'min_workers': cfg.autoscale_min_workers,
                      'coverage': coverage,
                      'cooldown_remaining_s': round(cooldown_left, 3)}
            if now < self._cooldown_until:
                self.suppressed += 1
                _decisions.record_decision(
                    'autoscaler', 'hold', 'autoscale_cooldown_s',
                    dict(inputs, want=1, wanted='scale_in'),
                    suppressed=True, cooldown_until=self._cooldown_until,
                    journal=self.decisions)
                return None
            victim = self._drain_victim(alive, coverage)
            self.scale_ins += 1
            self._after_action('scale_in', now)
            self._idle_since = None
            _decisions.record_decision(
                'autoscaler', 'scale_in', 'autoscale_idle_s', inputs,
                cooldown_until=self._cooldown_until, worker_id=victim,
                journal=self.decisions)
            self.launcher.notify_drain(victim)
            return ('scale_in', victim)
        return None

    def _after_action(self, action, now):
        self.last_action = action
        self.last_action_t = now
        self._cooldown_until = now + self.config.autoscale_cooldown_s

    @staticmethod
    def _drain_victim(alive, coverage):
        """The alive worker whose departure costs the least cache
        directory coverage (fewest advertised digests; id-ordered
        tie-break for determinism)."""
        coverage = coverage or {}
        return min(alive, key=lambda wid: (coverage.get(wid, 0), wid))

    def snapshot(self):
        """Counters for the ``stats`` rollup / fleet snapshot."""
        return {'enabled': self.enabled,
                'killed': killed(),
                'scale_outs': self.scale_outs,
                'scale_ins': self.scale_ins,
                'actions': self.actions,
                'suppressed': self.suppressed,
                'last_action': self.last_action}

    def close(self):
        try:
            self.launcher.close()
        except Exception:  # noqa: BLE001 — shutdown must not raise
            logger.exception('autoscaler launcher close failed')
