"""Worker contract shared by all pools.

Parity: reference ``petastorm/workers_pool/worker_base.py :: WorkerBase``.
"""


class WorkerBase(object):
    """A unit-of-work processor owned by one pool slot.

    ``publish_func(result)`` pushes zero or more results per work item to the
    pool's results queue.  Subclasses implement ``process(*args)``.
    """

    def __init__(self, worker_id, publish_func, args):
        self.worker_id = worker_id
        self.publish_func = publish_func
        self.args = args
        #: The owning pool's ``MetricsRegistry``: the pool sets it right
        #: after construction, so what a worker times (row-group read,
        #: codec decode) lands beside the pool's own counters.
        self.metrics = None

    def process(self, *args, **kwargs):
        raise NotImplementedError()

    def publish_func(self, data):  # overwritten by __init__; here for linters
        raise NotImplementedError()

    def shutdown(self):
        """Called once when the pool stops; release per-worker resources."""
