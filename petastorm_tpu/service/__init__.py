"""Disaggregated data-loading service: decode on fleet hosts, train on TPUs.

For the delivery-bound regime: one host's decode/collate plane cannot
feed the chips.  This subsystem scales the decode plane horizontally
and independently of the training hosts — the architecture of tf.data's
data service (arxiv 2101.12127) realized over this repo's own reader/pool
machinery:

* :class:`~petastorm_tpu.service.dispatcher.Dispatcher` — control plane:
  partitions the row-group list into splits, leases them to workers,
  reassigns on lease expiry (worker death).
* :class:`~petastorm_tpu.service.worker.Worker` — decode plane: wraps the
  existing readers over each leased split and streams serialized batches
  (Arrow IPC / pickle, the ProcessPool wire formats) under credit-based
  backpressure.
* :class:`~petastorm_tpu.service.client.ServiceDataLoader` — delivery
  plane: a drop-in ``petastorm_tpu.jax.DataLoader`` peer with the same
  sharding default (``jax.process_index()``) and resume-token contract,
  committing whole splits exactly once.
* ``petastorm_tpu.service.cluster`` — the cluster cache tier (ISSUE
  10): cache-affinity lease routing, remote HIT serving, and peer fill
  over the epoch-cache plane's content-fingerprint digests (on by
  default with ``cache_plane=True``; kill switch
  ``PETASTORM_TPU_NO_CLUSTER_CACHE=1``).
* ``petastorm_tpu.service.ledger`` — the durable dispatcher ledger
  (ISSUE 15): crash-safe snapshot/restore of split states, attempt
  counters, and the cache directory (``ServiceConfig(ledger_path=)``),
  with held-claim reconciliation so a dispatcher restart resumes the
  epoch instead of re-decoding the world.  Workers drain gracefully on
  SIGTERM / the ``drain`` RPC, and ``petastorm-tpu-chaos``
  (``test_util/chaos.py``) is the scenario matrix proving digest +
  exactly-once + zero residue under injected faults.
* ``petastorm_tpu.service.tenancy`` — the multi-tenant serving tier
  (ISSUE 16): several consumers with distinct datasets/configs share
  one worker fleet.  Co-tenant jobs register at runtime
  (:func:`~petastorm_tpu.service.client.register_tenant_job`, consumed
  with ``ServiceDataLoader(tenant=...)``), lease grants are
  weighted-deficit-round-robin fair across tenants (composing with the
  cache-affinity split pick), admission is bounded
  (``max_tenant_jobs``, structured ``retry_after_s`` refusals), and
  per-tenant shm/cache byte quotas degrade — never stall — the
  over-budget tenant.
* ``petastorm_tpu.service.autoscaler`` — the closed-loop fleet
  autoscaler (ISSUE 16): an in-dispatcher tick controller
  (``ServiceConfig(autoscale=True)``) that scales out on sustained
  lease starvation through a pluggable ``WorkerLauncher`` and scales in
  through the graceful drain path (least cache-coverage victim), damped
  by cooldown/step/min-max bounds; kill switch
  ``PETASTORM_TPU_NO_AUTOSCALE=1``.

Console entry point: ``petastorm-tpu-data-service`` (see
``petastorm_tpu/service/cli.py``).
"""

from petastorm_tpu.service.client import (ServiceDataLoader,  # noqa: F401
                                          ServiceReader,
                                          register_tenant_job)
from petastorm_tpu.service.config import ServiceConfig  # noqa: F401
from petastorm_tpu.service.dispatcher import Dispatcher  # noqa: F401
from petastorm_tpu.service.worker import Worker  # noqa: F401
