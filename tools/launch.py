#!/usr/bin/env python
"""Distributed launcher (parity: the reference's tools/launch.py over
dmlc_tracker — SURVEY.md §3.4).

Spawns N worker processes for `--launcher local` (multi-process on one
box — the way distributed training is tested without a cluster, parity:
dmlc_tracker/local.py) or prints per-host commands for `--launcher
manual` (run one per host; ssh/mpi orchestration is intentionally left to
the cluster scheduler — on TPU pods the platform runner starts one
process per host already, so this launcher mainly serves CPU/GPU test
rigs and local development).

Env contract (consumed by mxnet_tpu.kvstore.init_distributed):
  DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT — coordinator address
  DMLC_NUM_WORKER                      — number of processes
  DMLC_WORKER_ID                       — this process's rank
  JAX_PLATFORMS                        — the platform, from --platform

Every rank is told its platform explicitly (--platform, default cpu). A
chip belongs to one process at a time and this launcher cannot give each
local rank a chip of its own, so `--launcher local` refuses to start
more than one rank on anything but the CPU instead of letting them hang
on each other.

Usage:
  python tools/launch.py -n 4 python train.py --kv-store dist_sync
"""
import argparse
import os
import signal
import socket
import subprocess
import sys


def _free_port():
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("-n", "--num-workers", type=int, required=True)
    ap.add_argument("--launcher", choices=("local", "manual"),
                    default="local")
    ap.add_argument("--host", default="127.0.0.1",
                    help="coordinator host (rank 0's address)")
    ap.add_argument("--port", type=int, default=0,
                    help="coordinator port (0 = pick a free one)")
    ap.add_argument("--platform", default="cpu",
                    help="JAX_PLATFORMS given to every rank (default cpu)")
    ap.add_argument("--env", action="append", default=[],
                    help="extra KEY=VALUE for workers (repeatable)")
    ap.add_argument("command", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)
    if not args.command:
        ap.error("no worker command given")
    port = args.port or _free_port()

    def worker_env(rank):
        env = dict(os.environ)
        env["DMLC_PS_ROOT_URI"] = args.host
        env["DMLC_PS_ROOT_PORT"] = str(port)
        env["DMLC_NUM_WORKER"] = str(args.num_workers)
        env["DMLC_WORKER_ID"] = str(rank)
        env["DMLC_ROLE"] = "worker"
        for kv in args.env:
            k, _, v = kv.partition("=")
            env[k] = v
        env["JAX_PLATFORMS"] = args.platform
        return env

    if args.launcher == "manual":
        for r in range(args.num_workers):
            ev = (f"DMLC_PS_ROOT_URI={args.host} DMLC_PS_ROOT_PORT={port} "
                  f"DMLC_NUM_WORKER={args.num_workers} DMLC_WORKER_ID={r}")
            print(f"[host {r}] {ev} {' '.join(args.command)}")
        return 0

    if args.platform != "cpu" and args.num_workers > 1:
        ap.error(f"--launcher local cannot give each of {args.num_workers} "
                 f"ranks its own {args.platform} chip (one process holds a "
                 "chip at a time); use --platform cpu, one rank, or "
                 "--launcher manual with one process per host")
    procs = [subprocess.Popen(args.command, env=worker_env(r))
             for r in range(args.num_workers)]

    def _kill(*_):
        for p in procs:
            if p.poll() is None:
                p.terminate()

    signal.signal(signal.SIGINT, _kill)
    signal.signal(signal.SIGTERM, _kill)
    rc = 0
    for p in procs:
        p.wait()
        rc = rc or p.returncode
    if rc:
        _kill()
    return rc


if __name__ == "__main__":
    sys.exit(main())
