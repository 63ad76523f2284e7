"""The timed path of a cell, built once: the system's reader and loader, the
jitted step with its state, and the loop that drives them.

``run.py`` builds one :class:`TimedPath`, drives it from the seed through its
first steps (which the reference follows) and hands the same object to the
window.  ``read_limits.py`` and the tests drive the same object, so what they
read is what a run compares.
"""

import time

import oracle

#: Batches of the window kept on the device (by reference, no copy) and
#: compared with the stored rows once the window has closed.
SAMPLED_BATCHES = 3
STEP_NAME = 'pt_bench_train_step'


def open_loader(config, traffic, data, seed, tiny=False):
    """The system's reader over ``data`` inside the loader that the traffic
    mix names, with the mix's and the configuration's arguments."""
    import petastorm_tpu.jax as loaders
    reader = config.open_reader('file://' + data, seed, traffic['reader_epochs'])
    args = dict(traffic['loader_args'],
                **(traffic.get('tiny_loader_args', {}) if tiny else {}))
    args = {k: (seed % (2 ** 31) if v == '$seed' else v) for k, v in args.items()}
    args.update(config.loader_kwargs())
    return getattr(loaders, traffic['loader'])(
        reader, batch_size=config.batch, **args)


class TimedPath(object):
    def __init__(self, config, traffic, data, seed, key, tiny=False):
        self.config, self.traffic, self.key = config, traffic, key
        self.step, init_state, self.norms = self.programs()
        self.state = init_state(key)
        self.loader = open_loader(config, traffic, data, seed, tiny)
        #: every row id delivered from the first step on (device arrays)
        self.ids = []
        self.first_batches, self.first_losses = [], []
        self.grad_norms = self.change_norms = None

    def program(self):
        """The step as the configuration gives it: ``(state, batch) -> (state,
        {'loss': the batch's mean, 'sample_loss': one for each row})``."""
        return self.config.train_step()

    def programs(self):
        """The jitted step, the weights' generator and the norms, made once
        for a configuration object (``read_limits.py`` drives many seeds)."""
        import jax
        made = getattr(self.config, '_timed_path_programs', None)
        if made is None or made[0] != self.program.__func__:
            program = self.program()
            program.__name__ = program.__qualname__ = STEP_NAME
            made = (self.program.__func__,
                    jax.jit(program, donate_argnums=(0,) if self.config.donate_state
                            else ()),
                    jax.jit(self.config.init_state), oracle.LeafNorms(self.config))
            self.config._timed_path_programs = made
        return made[1:]

    def __enter__(self):
        self.loader.__enter__()
        self.it = self.iterate(self.loader)
        return self

    def __exit__(self, *exc):
        return self.loader.__exit__(*exc)

    def iterate(self, loader):
        return iter(loader)

    def next_batch(self):
        batch = next(self.it)
        self.ids.append(self.config.row_ids(batch))
        return batch

    def first_steps(self, n):
        """What set-up must fill, then the first ``n`` steps: through the
        window's own call and feed, keeping what the reference is compared
        with."""
        import jax
        config, traffic = self.config, self.traffic
        filled = None
        for _ in range(traffic['fill_epochs'] * (config.rows // config.batch)):
            filled = next(self.it)
        jax.block_until_ready(filled)
        del filled
        for i in range(n):
            batch = self.next_batch()
            self.first_batches.append(batch)
            self.state, out = self.step(self.state, batch)
            self.first_losses.append(out)
            if i == 0:
                self.grad_norms = self.norms.first_gradient(self.state, self.key)
        self.change_norms = self.norms.change(self.state, self.key)

    def settle(self):
        for _ in range(self.traffic['settle_steps']):
            self.state, out = self.step(self.state, self.next_batch())
        out['loss'].block_until_ready()

    def window(self, seconds, rng):
        """The measured window: a closed loop of one training loop.  Dispatch
        runs one step ahead of ``block_until_ready`` so the device keeps a step
        queued; a step counts when its loss is ready."""
        sampled, step_s, wait_s, seen, pending = [], [], 0.0, 0, None
        t0 = last_done = time.monotonic()
        deadline = t0 + seconds
        while True:
            t_wait = time.monotonic()
            batch = self.next_batch()
            now = time.monotonic()
            wait_s += now - t_wait
            self.state, out = self.step(self.state, batch)
            # a seeded reservoir of the window's batches, held by reference
            seen += 1
            if len(sampled) < SAMPLED_BATCHES:
                sampled.append(batch)
            else:
                slot = int(rng.integers(0, seen))
                if slot < SAMPLED_BATCHES:
                    sampled[slot] = batch
            del batch
            if pending is not None:
                pending.block_until_ready()
                now = time.monotonic()
                step_s.append(now - last_done)
                last_done = now
            pending = out['loss']
            if now >= deadline:
                break
        pending.block_until_ready()
        now = time.monotonic()
        step_s.append(now - last_done)
        return {'window_s': now - t0, 'steps': len(step_s), 'wait_s': wait_s,
                'step_s': step_s, 'sampled': sampled}

    def readings(self):
        """The first steps' numbers and batches, on the host."""
        import jax
        outs = jax.device_get(self.first_losses)
        return ({'losses': [float(out['loss']) for out in outs],
                 'sample_losses': [out['sample_loss'] for out in outs],
                 'grad_norms': jax.device_get(self.grad_norms),
                 'change_norms': jax.device_get(self.change_norms)},
                [jax.device_get(b) for b in self.first_batches])

    def free(self):
        """Drops the program's state and everything held on the device."""
        self.state = self.first_batches = self.first_losses = None
        self.grad_norms = self.change_norms = self.ids = None
