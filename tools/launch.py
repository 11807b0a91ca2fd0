#!/usr/bin/env python
"""Distributed job launcher — capability parity with reference
``tools/launch.py`` (dmlc_tracker ssh/mpi/sge/yarn/local, :29,48-115), shaped
for the TPU runtime: instead of scheduler/server/worker roles over ps-lite,
every process is an equal jax.distributed participant; process 0 hosts the
coordination service (SURVEY §5.8 translation: the launcher becomes a thin
multi-host bootstrapper).

Usage (mirrors the reference CLI):

    # N local processes, a fake cluster on one host (the reference's
    # `--launcher local` nightly-test pattern, ci/runtime_functions.sh:673)
    JAX_PLATFORMS=cpu python tools/launch.py -n 4 --launcher local \
        python train.py ...

    # ssh to a host list; each host runs one process
    python tools/launch.py -n 4 -H hostfile --launcher ssh python train.py ...

Every spawned process receives the env contract consumed by
``mxnet_tpu.parallel.dist.init()``:
  MXNET_COORDINATOR, MXNET_NUM_WORKERS, MXNET_WORKER_RANK
(DMLC_* aliases are exported too for scripts reading the reference names).
Observability env (MXNET_TELEMETRY / MXNET_TRACE / MXNET_FLIGHTREC_DIR /
MXNET_POD_METRICS*) set on the launcher is propagated to every worker, and
each worker's stdout/stderr is line-prefixed with ``[rank N]`` so pod logs
stay attributable (ISSUE 19 satellite).

**One process per chip.**  A TPU chip belongs to one process at a time, and
every child of the local launcher would see — and claim — every chip of the
host.  The local launcher does not pin children to chips: on a host with TPU
chips it REFUSES ``-n`` above 1 unless ``JAX_PLATFORMS`` keeps the children
off the TPU (the CPU fake cluster above).  One process drives all the chips
of a host through a mesh; several hosts go through ``--launcher ssh``, one
process each.  For the same reason this launcher never imports jax: a parent
that has touched JAX holds the chip its child needs.
"""
from __future__ import annotations

import argparse
import glob
import os
import signal
import socket
import subprocess
import sys
import threading

# observability + caching env propagated from the launcher to every worker
# (ISSUE 19/20 satellites): exact names plus prefix families.  The ssh
# launcher builds worker env from scratch (base={}), so without this an
# operator exporting MXNET_TELEMETRY=1 before launch gets silent per-worker
# no-ops.  The MXNET_AOT_CACHE / MXNET_AUTOTUNE prefixes cover the whole
# families (…_MAX_MB, …_CACHE, …_MODEL, …_TOPK): an operator pointing the
# AOT/autotune caches at shared storage must have every rank see them, or
# a pod restart is warm on rank 0 and cold everywhere else.
_PROPAGATE_EXACT = ("MXNET_TELEMETRY", "MXNET_TRACE", "MXNET_FLIGHTREC_DIR",
                    "JAX_COMPILATION_CACHE_DIR")
_PROPAGATE_PREFIX = ("MXNET_POD_METRICS", "MXNET_AOT_CACHE",
                     "MXNET_AUTOTUNE", "MXNET_ELASTIC")


def _free_port():
    s = socket.socket()
    s.bind(("", 0))
    port = s.getsockname()[1]
    s.close()
    return port


def _env_for(rank, n, coordinator, base=None):
    env = dict(base if base is not None else os.environ)
    for k, v in os.environ.items():
        if k in _PROPAGATE_EXACT or k.startswith(_PROPAGATE_PREFIX):
            env.setdefault(k, v)
    env.update({
        "MXNET_COORDINATOR": coordinator,
        "MXNET_NUM_WORKERS": str(n),
        "MXNET_WORKER_RANK": str(rank),
        # reference names, for scripts that read them
        "DMLC_NUM_WORKER": str(n),
        "DMLC_RANK": str(rank),
        "DMLC_ROLE": "worker",
    })
    return env


def _pump(stream, rank, out):
    """Copy one worker's merged stdout/stderr to ``out``, prefixing every
    line with ``[rank N]`` so interleaved pod logs stay attributable."""
    prefix = "[rank %d] " % rank
    for line in iter(stream.readline, ""):
        out.write(prefix + line)
        out.flush()
    stream.close()


def _spawn_prefixed(cmd, rank, env=None):
    """Popen with stderr merged into stdout and a daemon pump thread that
    rank-prefixes every line.  Line-buffered text mode: a worker writing
    whole lines (the logging default) is never split mid-line."""
    p = subprocess.Popen(cmd, env=env, stdout=subprocess.PIPE,
                         stderr=subprocess.STDOUT, text=True, bufsize=1)
    t = threading.Thread(target=_pump, args=(p.stdout, rank, sys.stdout),
                         name="launch-pump-%d" % rank, daemon=True)
    t.start()
    return p, t


def _children_would_share_tpu():
    """True when children started here would each open this host's TPU
    chips: the chips' device nodes exist and ``JAX_PLATFORMS`` does not keep
    jax off them.  Decided without importing jax (module docstring)."""
    platforms = os.environ.get("JAX_PLATFORMS", "").strip().lower()
    if platforms and "tpu" not in platforms.split(","):
        return False
    return bool(glob.glob("/dev/accel*") or glob.glob("/dev/vfio/[0-9]*"))


def launch_local(n, command, verbose=False):
    """N processes on this host (the reference local tracker)."""
    if n > 1 and _children_would_share_tpu():
        raise SystemExit(
            "launch.py --launcher local -n %d: this host has TPU chips and "
            "every child would claim all of them (a chip belongs to one "
            "process).  Run ONE process that drives the chips through a "
            "mesh, or set JAX_PLATFORMS=cpu for a CPU fake cluster." % n)
    coordinator = "127.0.0.1:%d" % _free_port()
    procs, pumps = [], []
    try:
        for rank in range(n):
            p, t = _spawn_prefixed(command, rank,
                                   env=_env_for(rank, n, coordinator))
            procs.append(p)
            pumps.append(t)
        codes = [p.wait() for p in procs]
    except KeyboardInterrupt:
        for p in procs:
            p.send_signal(signal.SIGTERM)
        raise
    for t in pumps:
        t.join(timeout=5.0)
    bad = [(i, c) for i, c in enumerate(codes) if c != 0]
    if bad:
        raise SystemExit("workers failed: %s" % bad)
    return 0


def launch_ssh(n, hosts, command, verbose=False, port=None):
    """One process per host over ssh (reference ssh launcher, launch.py:48).

    The coordinator address is host0:port. The port must be free ON hosts[0]
    — a locally-probed free port proves nothing about the remote — so a fixed
    default is used and --port overrides it on conflict.
    """
    if len(hosts) < n:
        raise SystemExit("need %d hosts, hostfile has %d" % (n, len(hosts)))
    port = port or 29400
    coordinator = "%s:%d" % (hosts[0], port)
    cmd_str = " ".join("'%s'" % c for c in command)
    procs, pumps = [], []
    for rank in range(n):
        envs = " ".join(
            "%s=%s" % (k, v)
            for k, v in _env_for(rank, n, coordinator, base={}).items()
        )
        full = ["ssh", "-o", "StrictHostKeyChecking=no", hosts[rank],
                "cd %s && env %s %s" % (os.getcwd(), envs, cmd_str)]
        if verbose:
            print("launch:", " ".join(full))
        p, t = _spawn_prefixed(full, rank)
        procs.append(p)
        pumps.append(t)
    codes = [p.wait() for p in procs]
    for t in pumps:
        t.join(timeout=5.0)
    bad = [(hosts[i], c) for i, c in enumerate(codes) if c != 0]
    if bad:
        raise SystemExit("workers failed: %s" % bad)
    return 0


def main():
    parser = argparse.ArgumentParser(
        description="Launch a distributed mxnet_tpu job",
        formatter_class=argparse.ArgumentDefaultsHelpFormatter,
    )
    parser.add_argument("-n", "--num-workers", required=True, type=int,
                        help="number of worker processes")
    parser.add_argument("-s", "--num-servers", type=int, default=0,
                        help="accepted for reference CLI parity; the collective "
                             "runtime has no server role, so this is ignored")
    parser.add_argument("-H", "--hostfile", type=str,
                        help="file with one hostname per line (ssh launcher)")
    parser.add_argument("--launcher", type=str, default="local",
                        choices=["local", "ssh"],
                        help="mpi/sge/yarn launchers of the reference are "
                             "cluster-manager specific; local and ssh cover "
                             "the dev and bare-metal paths")
    parser.add_argument("--port", type=int, default=None,
                        help="coordinator port on host 0 (ssh launcher)")
    parser.add_argument("--verbose", action="store_true")
    parser.add_argument("command", nargs=argparse.REMAINDER,
                        help="command to run on every worker")
    args = parser.parse_args()
    if not args.command:
        parser.error("no command given")
    if args.num_servers:
        print("note: -s/--num-servers ignored — collectives replace the "
              "parameter-server role (see SURVEY §5.8)", file=sys.stderr)
    if args.launcher == "local":
        return launch_local(args.num_workers, args.command, args.verbose)
    if not args.hostfile:
        parser.error("--hostfile is required with --launcher ssh")
    hosts = [h.strip() for h in open(args.hostfile) if h.strip()]
    return launch_ssh(args.num_workers, hosts, args.command, args.verbose,
                      port=args.port)


if __name__ == "__main__":
    sys.exit(main())
