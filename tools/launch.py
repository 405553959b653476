#!/usr/bin/env python
"""Distributed job launcher.

Reference analog: ``tools/launch.py:72`` (dmlc-tracker: spawns scheduler +
servers + workers over local/ssh/mpi with DMLC_* env).  TPU-native jobs are
multi-controller JAX: N identical worker processes, process 0 doubling as
the coordination point — no scheduler/server processes needed (collectives
replace the parameter server).  Supported launchers:

  local  N CPU worker processes on this machine (how the reference tests
         multi-node without a cluster, tests/nightly/dist_sync_kvstore.py).
         Workers get JAX_PLATFORMS=cpu: a chip belongs to ONE process, so
         N ranks inheriting a chip host's environment would each open
         every chip and hang.  Override with --env JAX_PLATFORMS=...
  ssh    one worker per host from --host-file
  mpi    one worker per MPI rank via ``mpirun``; ranks map their
         OMPI_COMM_WORLD_RANK / PMI_RANK onto the same env contract
         (reference tools/launch.py mpi submission)
  sge    a Sun Grid Engine array job via ``qsub -t 1-N``; rank =
         SGE_TASK_ID - 1 (reference dmlc-tracker sge)
  yarn   one worker per YARN container via the ``yarn`` CLI's
         distributed-shell; requires HADOOP_HOME and a reachable RM
         (reference dmlc-tracker yarn; on TPU fleets prefer GKE — this
         mode exists for parity with Hadoop clusters)

Each worker gets MXNET_TPU_COORDINATOR / MXNET_TPU_NUM_PROCS /
MXNET_TPU_PROC_ID, consumed by ``mxnet_tpu.kvstore.kvstore_server
.init_distributed``.
"""
from __future__ import annotations

import argparse
import os
import shlex
import subprocess
import sys
import threading

_PRINT_LOCK = threading.Lock()


def _relay(stream, sink):
    """Copy a worker's output line-atomically onto our own stream.

    Workers share the launcher's stdout; concurrent writes from separate
    processes interleave mid-line on a pipe (observed: ``RANKRANK 1\\n 0\\n``),
    which corrupts any consumer parsing lines.  One reader thread per worker
    + a print lock keeps every line intact."""
    for line in iter(stream.readline, b""):
        with _PRINT_LOCK:
            sink.buffer.write(line)
            sink.flush()
    stream.close()


def _wait_all(procs, relay_threads):
    # wait for workers FIRST: a worker may leave a background child holding
    # its stdout pipe open, in which case the relay thread never sees EOF —
    # bounded joins after exit drain what's left without hanging the launcher
    rcs = [p.wait() for p in procs]
    for t in relay_threads:
        t.join(timeout=5.0)
    bad = [(i, rc) for i, rc in enumerate(rcs) if rc]
    if bad:
        for i, rc in bad:
            print(f"launch.py: worker {i} exited with rc={rc}",
                  file=sys.stderr)
        sys.exit(bad[0][1])
    sys.exit(0)


def _mpi_shim(coordinator: str, command):
    """Exec'd once per MPI rank (by ``mpirun``): translate the MPI
    launcher's rank/size env onto the MXNET_TPU_* contract, then exec the
    user command.  Open MPI exports OMPI_COMM_WORLD_*; MPICH/Slurm-PMI
    export PMI_*."""
    env = os.environ
    rank = env.get("OMPI_COMM_WORLD_RANK", env.get("PMI_RANK",
                   env.get("MV2_COMM_WORLD_RANK")))
    size = env.get("OMPI_COMM_WORLD_SIZE", env.get("PMI_SIZE",
                   env.get("MV2_COMM_WORLD_SIZE")))
    if rank is None or size is None:
        sys.exit("launch.py --mpi-shim: no MPI rank env found "
                 "(OMPI_COMM_WORLD_RANK / PMI_RANK) — run under mpirun")
    os.environ.update({
        "MXNET_TPU_COORDINATOR": coordinator,
        "MXNET_TPU_NUM_PROCS": size,
        "MXNET_TPU_PROC_ID": rank,
        "DMLC_ROLE": "worker",
        "DMLC_NUM_WORKER": size,
        "DMLC_WORKER_ID": rank,
    })
    os.execvp(command[0], command)


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("-s", "--num-servers", type=int, default=0,
                    help="accepted for reference parity; TPU jobs need no "
                         "servers (0 spawned unless explicitly requested)")
    ap.add_argument("--launcher",
                    choices=["local", "ssh", "mpi", "sge", "yarn"],
                    default="local")
    ap.add_argument("-H", "--host-file", default=None)
    ap.add_argument("--port", type=int, default=29500)
    ap.add_argument("--env", action="append", default=[],
                    help="extra VAR=VAL for every worker")
    ap.add_argument("--mpi-shim", action="store_true",
                    help=argparse.SUPPRESS)
    ap.add_argument("--coordinator", default=None, help=argparse.SUPPRESS)
    ap.add_argument("--sge-queue", default=None,
                    help="SGE queue to submit to (sge launcher)")
    ap.add_argument("--coordinator-host", default=None,
                    help="host rank 0 binds on, as reachable from the "
                         "cluster (sge/yarn; default: this machine's "
                         "FQDN)")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args()
    if not args.command:
        ap.error("no command given")
    if args.command and args.command[0] == "--":
        args.command = args.command[1:]
    if args.mpi_shim:
        return _mpi_shim(args.coordinator or "127.0.0.1:29500",
                         args.command)

    n = args.num_workers
    coordinator = f"127.0.0.1:{args.port}"
    extra_env = dict(e.split("=", 1) for e in args.env)

    if args.launcher == "local":
        procs, threads = [], []
        for rank in range(n):
            env = dict(os.environ)
            # N processes on ONE host: a chip belongs to one process, and
            # every rank inheriting a chip host's environment would open
            # every chip and hang.  Local workers are CPU workers unless
            # --env JAX_PLATFORMS=... says otherwise.
            env["JAX_PLATFORMS"] = "cpu"
            env.update(extra_env)
            env.update({
                "MXNET_TPU_COORDINATOR": coordinator,
                "MXNET_TPU_NUM_PROCS": str(n),
                "MXNET_TPU_PROC_ID": str(rank),
                "DMLC_ROLE": "worker",
                # reference-compat aliases
                "DMLC_NUM_WORKER": str(n),
                "DMLC_WORKER_ID": str(rank),
            })
            p = subprocess.Popen(args.command, env=env,
                                 stdout=subprocess.PIPE,
                                 stderr=subprocess.PIPE)
            procs.append(p)
            for stream, sink in ((p.stdout, sys.stdout),
                                 (p.stderr, sys.stderr)):
                t = threading.Thread(target=_relay, args=(stream, sink),
                                     daemon=True)
                t.start()
                threads.append(t)
        _wait_all(procs, threads)

    if args.launcher == "mpi":
        import shutil

        mpirun = shutil.which("mpirun") or shutil.which("mpiexec")
        if not mpirun:
            sys.exit("launch.py: --launcher mpi needs mpirun/mpiexec on "
                     "PATH")
        if args.host_file:
            with open(args.host_file) as f:
                first = next((h.strip() for h in f if h.strip()), None)
            coordinator = f"{first}:{args.port}" if first else coordinator
        cmd = [mpirun, "-np", str(n)]
        if args.host_file:
            cmd += ["--hostfile", args.host_file]
        try:
            ver = subprocess.run([mpirun, "--version"],
                                 capture_output=True, text=True,
                                 timeout=10).stdout
        except Exception:
            ver = ""
        for k, v in extra_env.items():
            if "Open MPI" in ver or "OpenRTE" in ver:
                cmd += ["-x", f"{k}={v}"]        # Open MPI spelling
            else:
                cmd += ["-genv", k, v]           # Hydra (MPICH/Intel MPI)
        cmd += [sys.executable, os.path.abspath(__file__), "-n", str(n),
                "--mpi-shim", "--coordinator", coordinator, "--"]
        cmd += args.command
        p = subprocess.Popen(cmd)
        sys.exit(p.wait())

    if args.launcher in ("sge", "yarn"):
        # workers land on other nodes: 127.0.0.1 can never rendezvous —
        # rank 0 must bind an address the cluster can reach
        import socket

        host = args.coordinator_host or socket.getfqdn()
        coordinator = f"{host}:{args.port}"

    if args.launcher == "sge":
        import shutil
        import tempfile

        if not shutil.which("qsub"):
            sys.exit("launch.py: --launcher sge needs qsub on PATH")
        envs = "\n".join(
            f"export {k}={shlex.quote(v)}" for k, v in {
                **extra_env,
                "MXNET_TPU_COORDINATOR": coordinator,
                "MXNET_TPU_NUM_PROCS": str(n),
                "DMLC_ROLE": "worker",
            }.items())
        cmd = " ".join(shlex.quote(c) for c in args.command)
        script = (f"#!/bin/bash\n#$ -cwd\n#$ -V\n{envs}\n"
                  "export MXNET_TPU_PROC_ID=$((SGE_TASK_ID - 1))\n"
                  "export DMLC_WORKER_ID=$MXNET_TPU_PROC_ID\n"
                  f"exec {cmd}\n")
        with tempfile.NamedTemporaryFile("w", suffix=".sh",
                                         delete=False) as f:
            f.write(script)
            path = f.name
        qsub = ["qsub", "-sync", "y", "-t", f"1-{n}"]
        if args.sge_queue:
            qsub += ["-q", args.sge_queue]
        sys.exit(subprocess.call(qsub + [path]))

    if args.launcher == "yarn":
        import shutil

        if not shutil.which("yarn"):
            sys.exit(
                "launch.py: --launcher yarn needs the Hadoop 'yarn' CLI "
                "(HADOOP_HOME) — on TPU fleets prefer GKE/xpk, or use "
                "--launcher ssh/mpi")
        cmd = " ".join(shlex.quote(c) for c in args.command)
        envs = ",".join(
            f"{k}={v}" for k, v in {
                **extra_env,
                "MXNET_TPU_COORDINATOR": coordinator,
                "MXNET_TPU_NUM_PROCS": str(n),
                "DMLC_ROLE": "worker",
            }.items())
        # distributed-shell: one container per worker; the container id
        # env CONTAINER_ID's last field - 1 is the rank
        # container _000001 is the distributed-shell AM; workers are
        # _000002.. => rank = id - 2.  10# forces base-10 (zero-padded
        # suffixes like 000008 would otherwise parse as bad octal).
        shell = ("export MXNET_TPU_PROC_ID=$((10#${CONTAINER_ID##*_} - 2));"
                 " export DMLC_WORKER_ID=$MXNET_TPU_PROC_ID; " + cmd)
        jar = os.environ.get(
            "YARN_DSHELL_JAR",
            os.path.join(os.environ.get("HADOOP_HOME", ""),
                         "share/hadoop/yarn",
                         "hadoop-yarn-applications-distributedshell.jar"))
        sys.exit(subprocess.call(
            ["yarn", "jar", jar,
             "-jar", jar, "-num_containers", str(n),
             "-shell_env", envs, "-shell_command", shell]))

    # ssh launcher
    with open(args.host_file) as f:
        hosts = [h.strip() for h in f if h.strip()]
    if len(hosts) < n:
        sys.exit(f"need {n} hosts, have {len(hosts)}")
    coordinator = f"{hosts[0]}:{args.port}"
    procs, threads = [], []
    for rank, host in enumerate(hosts[:n]):
        envs = " ".join(
            f"{k}={shlex.quote(v)}" for k, v in {
                **extra_env,
                "MXNET_TPU_COORDINATOR": coordinator,
                "MXNET_TPU_NUM_PROCS": str(n),
                "MXNET_TPU_PROC_ID": str(rank),
                "DMLC_ROLE": "worker",
            }.items())
        cmd = " ".join(shlex.quote(c) for c in args.command)
        p = subprocess.Popen(
            ["ssh", "-o", "StrictHostKeyChecking=no", host,
             f"cd {shlex.quote(os.getcwd())} && {envs} {cmd}"],
            stdout=subprocess.PIPE, stderr=subprocess.PIPE)
        procs.append(p)
        for stream, sink in ((p.stdout, sys.stdout), (p.stderr, sys.stderr)):
            t = threading.Thread(target=_relay, args=(stream, sink),
                                 daemon=True)
            t.start()
            threads.append(t)
    _wait_all(procs, threads)


if __name__ == "__main__":
    main()
