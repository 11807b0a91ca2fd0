"""Multi-host bootstrap — replaces ``tools/launch.py`` + dmlc tracker.

The reference spawns scheduler/server/worker roles over ssh/mpi/yarn and wires
them through ps-lite (``src/kvstore/kvstore_dist.h:50-55``, SURVEY §3.5).  The
TPU-native design has no parameter servers: every host is a worker, and
``jax.distributed.initialize`` + DCN collectives replace the tracker and RPC.

Environment contract (mirrors the reference's DMLC_* env protocol):
  MXNET_COORDINATOR  — "host:port" of process 0 (≡ scheduler address)
  MXNET_NUM_WORKERS  — total process count (≡ DMLC_NUM_WORKER)
  MXNET_WORKER_RANK  — this process's rank   (≡ DMLC_RANK)
Standard TPU-pod env (Cloud TPU metadata) is auto-detected by JAX when these
are absent, so on real pods ``init()`` with no args is enough.
"""
from __future__ import annotations

import os

_initialized = False


class DeadNodeError(RuntimeError):
    """A collective timed out because specific ranks never arrived.

    The reference detects dead nodes at barrier setup via the scheduler
    heartbeat (``ps::Postoffice::GetDeadNodes``, kvstore_dist.h:110-118) and
    aborts with the dead node list; without this, a lost rank silently hangs
    the whole job.  Carries ``missing_ranks``.
    """

    def __init__(self, barrier_name, missing_ranks, timeout_ms):
        self.missing_ranks = list(missing_ranks)
        super().__init__(
            "barrier %r timed out after %d ms: rank(s) %s never reported "
            "arrival (dead-node check over the coordination service) — the "
            "process(es) most likely died or hung; restart the job "
            "(reference semantics: checkpoint + relaunch, SURVEY §5.3)"
            % (barrier_name, timeout_ms,
               ",".join(str(r) for r in self.missing_ranks)))


def init(coordinator_address=None, num_processes=None, process_id=None,
         initialization_timeout=None, **kw):
    """Initialize multi-host JAX.  Idempotent; no-op in single-process runs
    unless coordinator env/args are present.

    ``initialization_timeout`` (seconds; env ``MXNET_DIST_INIT_TIMEOUT``)
    bounds the startup rendezvous — with a rank missing at launch the
    survivors fail after this timeout instead of waiting forever (the
    reference's scheduler barrier behaves the same way via heartbeat
    timeouts, kvstore_dist.h:110-118).  Note jax's distributed client
    TERMINATES the process on rendezvous timeout (fatal log, not a
    catchable exception) — fail-fast semantics, not recoverable ones."""
    global _initialized
    if _initialized:
        return
    coordinator_address = coordinator_address or os.environ.get("MXNET_COORDINATOR")
    if num_processes is None and "MXNET_NUM_WORKERS" in os.environ:
        num_processes = int(os.environ["MXNET_NUM_WORKERS"])
    if process_id is None and "MXNET_WORKER_RANK" in os.environ:
        process_id = int(os.environ["MXNET_WORKER_RANK"])
    if coordinator_address is None and num_processes is None:
        # single-host; jax.distributed not needed
        _initialized = True
        return
    if initialization_timeout is None and "MXNET_DIST_INIT_TIMEOUT" in os.environ:
        initialization_timeout = int(os.environ["MXNET_DIST_INIT_TIMEOUT"])
    if initialization_timeout is not None:
        kw["initialization_timeout"] = int(initialization_timeout)
    import jax

    # CPU backend: select the Gloo collectives implementation BEFORE the
    # backend instantiates — without one the CpuClient rejects every
    # process-spanning computation ("Multiprocess computations aren't
    # implemented on the CPU backend"), which would make the pod-mesh
    # paths (fused step + ZeRO over a 2-process fake cluster, orbax
    # collective saves) untestable off-TPU.  Gated on an explicit CPU
    # platform selection so real TPU/GPU pods are untouched; the flag
    # only affects CPU client creation.
    plats = (os.environ.get("JAX_PLATFORMS")
             or os.environ.get("JAX_PLATFORM_NAME") or "")
    if "cpu" in plats.split(","):
        jax.config.update("jax_cpu_collectives_implementation", "gloo")

    jax.distributed.initialize(
        coordinator_address=coordinator_address,
        num_processes=num_processes,
        process_id=process_id,
        **kw,
    )
    _initialized = True


def rank():
    """This host's index (reference ``KVStore.rank``, ``kvstore_dist.h:106``)."""
    import jax

    return jax.process_index()


def size():
    """Number of hosts (reference ``KVStore.num_workers``)."""
    import jax

    return jax.process_count()


def is_coordinator():
    return rank() == 0


def kv_prefix_ranks(client, prefix):
    """{rank: value string} for every ``<prefix><rank>`` key published in
    the coordination-service KV store — ONE ``key_value_dir_get``.  The ONE
    implementation behind both :func:`barrier`'s arrival marks and the
    trainhealth heartbeat exchange; a failed RPC degrades to no keys (every
    rank absent)."""
    try:
        pairs = client.key_value_dir_get(prefix)
    except Exception:
        return {}
    out = {}
    for k, v in pairs:
        try:
            out[int(str(k).rsplit("/", 1)[-1])] = str(v)
        except ValueError:
            pass
    return out


_barrier_seq = 0


def barrier(name="mxnet_barrier", timeout_ms=None):
    """Block until every process arrives (reference ``KVStore::Barrier``,
    ``kvstore_dist.h:96``).

    ``timeout_ms`` defaults to env ``MXNET_DIST_BARRIER_TIMEOUT_MS`` (else
    120 s); an explicitly passed value always wins over the env, matching
    ``init()``'s precedence.  On timeout the coordination-service KV store
    is queried for per-rank arrival marks and a :class:`DeadNodeError`
    NAMING the non-arrived ranks is raised — the reference's dead-node
    check (``ps::Postoffice::GetDeadNodes`` at barrier setup,
    kvstore_dist.h:110-118) rebuilt on the TPU stack.  A lost rank
    therefore fails the job fast with a diagnostic instead of hanging it."""
    global _barrier_seq
    import jax

    if jax.process_count() == 1:
        return
    if timeout_ms is None:
        timeout_ms = int(os.environ.get("MXNET_DIST_BARRIER_TIMEOUT_MS", 120_000))
    client = getattr(jax._src.distributed.global_state, "client", None)
    if client is None:
        # jax moved the internals, or no coordination-service client (e.g.
        # proxy backends): fall back to an unbounded device sync
        from jax.experimental import multihost_utils

        multihost_utils.sync_global_devices(name)
        return
    # barrier() is collective, so every process sees the same sequence
    # number; keys (unlike TSL barrier ids) are single-use, hence the suffix
    _barrier_seq += 1
    mark = "mxt_arrived/%s/%d" % (name, _barrier_seq)
    my_mark = "%s/%d" % (mark, jax.process_index())
    try:
        client.key_value_set(my_mark, "1")
    except Exception:
        import warnings

        warnings.warn("dist.barrier: failed to publish arrival mark %r — "
                      "on timeout OTHER ranks may misreport this one as "
                      "dead" % my_mark)
    try:
        client.wait_at_barrier("%s_%d" % (name, _barrier_seq), int(timeout_ms))
    except Exception as exc:
        # who never arrived?  One shared KV prefix scan over the
        # arrival marks
        arrived = kv_prefix_ranks(client, mark + "/")
        missing = [r for r in range(jax.process_count())
                   if r not in arrived]
        if missing:
            raise DeadNodeError(name, missing, timeout_ms) from exc
        raise
    # passed: drop this rank's mark so coordinator KV state stays bounded
    # over long jobs (barriers can run every sync interval for days)
    try:
        client.key_value_delete(my_mark)
    except Exception:
        pass


def shutdown():
    global _initialized
    import jax

    if _initialized and jax.process_count() > 1:
        jax.distributed.shutdown()
    _initialized = False
