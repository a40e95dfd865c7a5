#!/usr/bin/env python
"""Multi-process / multi-host training launcher.

Reference: `tools/launch.py` (`:72-74`) — spawns the ps-lite scheduler,
servers, and workers for `kvstore='dist_*'` via local/ssh/mpi launchers.

TPU-native equivalent: SPMD has no scheduler/server roles; every process
is a worker running the same script.  This launcher spawns N processes
(`--launcher local`, the mode the reference CI uses for distributed tests)
wired for `jax.distributed.initialize()`:

  JAX_COORDINATOR_ADDRESS   host:port of process 0
  JAX_NUM_PROCESSES         N
  JAX_PROCESS_ID            0..N-1

On TPU hardware ONE process per host drives all of that host's chips (a
chip belongs to one process at a time), so a single host needs no launcher
at all.  `--launcher local` starts N processes on this host with no device
partition between them: it is for the CPU mesh (each process gets a slice
of virtual devices), mirroring how the reference tests dist kvstore with N
local processes (`tests/nightly/test_distributed_training-gpu.sh`) — N
local processes that each want the host's chips fail or hang.
`--launcher ssh -H hostfile` drives a real multi-host cluster the way the
reference's ssh launcher does: one peer process per host, env-wired over
the ssh command line (see examples/distributed/README.md for the
v5p-64-shaped invocation).

Examples:
  python tools/launch.py -n 4 --launcher local -- python train.py --kv-store tpu_ici
  python tools/launch.py -n 8 --launcher ssh -H hosts.txt -- python train.py
"""
from __future__ import annotations

import argparse
import os
import shlex
import socket
import subprocess
import sys

# mxlint: disable-file=env-read-at-trace-time -- launcher plumbing: forwards the caller's environment into worker processes before mxnet_tpu ever imports
__all__ = ["launch_local", "launch_ssh", "parse_hostfile"]


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def launch_local(num_workers, command, env_extra=None,
                 devices_per_worker=None):
    """Spawn `num_workers` local processes running `command`; returns the
    list of exit codes (reference local launcher semantics: fail if any
    worker fails)."""
    port = _free_port()
    procs = []
    for rank in range(num_workers):
        env = dict(os.environ)
        env.update(env_extra or {})
        env["JAX_COORDINATOR_ADDRESS"] = f"127.0.0.1:{port}"
        env["JAX_NUM_PROCESSES"] = str(num_workers)
        env["JAX_PROCESS_ID"] = str(rank)
        # reference-compatible names some scripts read
        env["DMLC_NUM_WORKER"] = str(num_workers)
        env["DMLC_WORKER_ID"] = str(rank)
        if devices_per_worker:
            env["XLA_FLAGS"] = (
                env.get("XLA_FLAGS", "") +
                f" --xla_force_host_platform_device_count={devices_per_worker}"
            ).strip()
        procs.append(subprocess.Popen(command, env=env))
    codes = [p.wait() for p in procs]
    return codes


def parse_hostfile(path):
    """One host per line (`#` comments allowed); `host slots=N` MPI-style
    suffixes are accepted and the slot count ignored — on TPU pods each
    host runs exactly one process (reference hostfile format:
    `tools/launch.py -H`, dmlc-tracker ssh launcher)."""
    hosts = []
    with open(path) as f:
        for line in f:
            line = line.split("#", 1)[0].strip()
            if line:
                hosts.append(line.split()[0])
    if not hosts:
        raise ValueError(f"hostfile {path} lists no hosts")
    return hosts


def launch_ssh(num_workers, command, hosts, coordinator_port=41299,
               env_extra=None, env_forward=(), ssh_binary="ssh",
               remote_cwd=None):
    """Spawn one process per host over ssh (reference
    `tools/launch.py:72-74` ssh launcher, re-wired for SPMD: no
    scheduler/server roles, every process is a peer).

    Ranks are assigned round-robin over ``hosts``; process 0's host serves
    as the JAX coordinator (must be reachable from every worker on
    ``coordinator_port``).  ssh does not forward the environment, so the
    JAX_* wiring plus any ``env_extra``/``env_forward`` variables are
    inlined into the remote command.  ``ssh_binary`` is swappable so tests
    can run the transport against a local shell
    (tests/test_launch_ssh.py)."""
    coordinator = f"{hosts[0]}:{coordinator_port}"
    base_env = {
        "JAX_COORDINATOR_ADDRESS": coordinator,
        "JAX_NUM_PROCESSES": str(num_workers),
        "DMLC_NUM_WORKER": str(num_workers),
    }
    base_env.update(env_extra or {})
    for key in env_forward:
        if key in os.environ:
            base_env.setdefault(key, os.environ[key])
    procs = []
    for rank in range(num_workers):
        host = hosts[rank % len(hosts)]
        env = dict(base_env)
        env["JAX_PROCESS_ID"] = str(rank)
        env["DMLC_WORKER_ID"] = str(rank)
        assigns = " ".join(f"{k}={shlex.quote(v)}" for k, v in
                           sorted(env.items()))
        payload = " ".join(shlex.quote(c) for c in command)
        # cd first, THEN apply env to the actual command — `env VARS cd
        # DIR && cmd` would bind the variables to `cd` and leave the
        # training process unwired
        remote = f"env {assigns} {payload}"
        if remote_cwd:
            remote = f"cd {shlex.quote(remote_cwd)} && {remote}"
        argv = [ssh_binary, "-o", "StrictHostKeyChecking=no",
                "-o", "BatchMode=yes", host, remote]
        procs.append(subprocess.Popen(argv))
    return [p.wait() for p in procs]


def main(argv=None):
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("-n", "--num-workers", type=int, required=True)
    p.add_argument("--launcher", choices=["local", "ssh"], default="local",
                   help="'local' spawns N processes on this machine (the "
                        "reference CI pattern); 'ssh' spawns one process "
                        "per hostfile entry (reference ssh launcher). "
                        "mpi/sge/yarn are out of scope: TPU pods schedule "
                        "through their own runtime or ssh")
    p.add_argument("-H", "--hostfile", type=str, default=None,
                   help="hostfile (one host per line), required for ssh")
    p.add_argument("--coordinator-port", type=int, default=41299,
                   help="port on host 0 for jax.distributed coordination")
    p.add_argument("--env", action="append", default=[],
                   help="KEY=VAL to set remotely, or bare KEY to forward "
                        "its current value (reference --env)")
    p.add_argument("--ssh-binary", default="ssh",
                   help="transport override (testing)")
    p.add_argument("--remote-cwd", default=None,
                   help="directory to cd into on each host before running")
    p.add_argument("--devices-per-worker", type=int, default=0,
                   help="virtual CPU devices per process (testing)")
    p.add_argument("--profile-rank", type=int, default=None,
                   help="profile worker rank N from the launcher "
                        "(reference: rank 0 toggling a server profiler "
                        "over a kvstore command, kvstore_dist.h:99); the "
                        "rank dumps profile_rank{N}.json at exit; -1 = "
                        "every rank")
    p.add_argument("--profile-dir", default=".",
                   help="directory for --profile-rank dumps")
    p.add_argument("command", nargs=argparse.REMAINDER,
                   help="training command (prefix with --)")
    args = p.parse_args(argv)
    command = args.command
    if command and command[0] == "--":
        command = command[1:]
    if not command:
        p.error("no command given")
    env_extra, env_forward = {}, []
    if args.profile_rank is not None:
        if args.profile_rank >= args.num_workers or args.profile_rank < -1:
            p.error(f"--profile-rank {args.profile_rank} out of range "
                    f"(ranks are 0..{args.num_workers - 1}, or -1 for all)")
        env_extra["MXNET_PROFILE_RANK"] = str(args.profile_rank)
        env_extra["MXNET_PROFILE_DIR"] = args.profile_dir
    for item in args.env:
        if "=" in item:
            k, v = item.split("=", 1)
            env_extra[k] = v
        else:
            env_forward.append(item)
    if args.launcher == "ssh":
        if not args.hostfile:
            p.error("--launcher ssh requires -H/--hostfile")
        hosts = parse_hostfile(args.hostfile)
        if args.devices_per_worker:
            env_extra.setdefault(
                "XLA_FLAGS",
                f"--xla_force_host_platform_device_count="
                f"{args.devices_per_worker}")
        codes = launch_ssh(args.num_workers, command, hosts,
                           coordinator_port=args.coordinator_port,
                           env_extra=env_extra, env_forward=env_forward,
                           ssh_binary=args.ssh_binary,
                           remote_cwd=args.remote_cwd)
    else:
        codes = launch_local(args.num_workers, command, env_extra=env_extra,
                             devices_per_worker=args.devices_per_worker or None)
    bad = [i for i, c in enumerate(codes) if c != 0]
    if bad:
        print(f"workers failed: {bad}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
