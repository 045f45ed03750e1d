"""Process launcher (reference: fleet/launch.py:334 launch(), process
management launch_utils.py:425 TrainerProc / :435 start_local_trainers /
:526 watch_local_trainers).

On TPU pods the unit is one process per HOST: every local chip belongs
to it (a chip serves one process at a time, and one process drives all
the chips of its host), coordinated across hosts by jax.distributed. So
the launcher starts ONE worker on this node and exports the same
PADDLE_* env protocol the reference uses, plus the jax coordinator
address. The reference's --nproc_per_node (one process per GPU) has no
TPU meaning and is not offered.

Usage: python -m paddle_tpu.distributed.launch --ips=host1,host2
           --node_rank=0 train.py [args...]
"""
from __future__ import annotations

import argparse
import os
import signal
import subprocess
import sys
import time

__all__ = ["launch", "start_local_trainers", "watch_local_trainers", "main"]


class TrainerProc:
    def __init__(self, proc, rank, log_file=None):
        self.proc = proc
        self.rank = rank
        self.log_file = log_file


def start_local_trainers(script, script_args, node_rank, nnodes, master,
                         log_dir=None, hosts=None):
    """Spawn this node's worker with the PADDLE_* env protocol
    (launch_utils.py:435): rank r lives on hosts[r]."""
    port = int(master.split(":")[1])
    hosts = hosts or [master.split(":")[0]] * nnodes
    env = dict(os.environ)
    env.update({
        "PADDLE_TRAINER_ID": str(node_rank),
        "PADDLE_TRAINERS_NUM": str(nnodes),
        "PADDLE_TRAINER_ENDPOINTS": ",".join(f"{h}:{port}" for h in hosts),
        "PADDLE_MASTER_ENDPOINT": master,
        "PADDLE_LOCAL_RANK": "0",
    })
    log = None
    if log_dir:
        os.makedirs(log_dir, exist_ok=True)
        log = open(os.path.join(log_dir, f"workerlog.{node_rank}"), "w")
    p = subprocess.Popen([sys.executable, script] + list(script_args),
                         env=env, stdout=log or None, stderr=log or None)
    return [TrainerProc(p, node_rank, log)]


def watch_local_trainers(procs, poll_s=1.0):
    """Abort all if any worker dies (launch_utils.py:526)."""
    try:
        while True:
            alive = False
            for tp in procs:
                ret = tp.proc.poll()
                if ret is None:
                    alive = True
                elif ret != 0:
                    for other in procs:
                        if other.proc.poll() is None:
                            other.proc.send_signal(signal.SIGTERM)
                    raise RuntimeError(
                        f"worker rank {tp.rank} exited with code {ret}")
            if not alive:
                return 0
            time.sleep(poll_s)
    finally:
        for tp in procs:
            if tp.log_file:
                tp.log_file.close()


def launch(args=None):
    parser = argparse.ArgumentParser("paddle_tpu.distributed.launch")
    parser.add_argument("--ips", type=str, default="127.0.0.1",
                        help="comma-separated host list")
    parser.add_argument("--node_rank", type=int,
                        default=int(os.environ.get("PADDLE_NODE_RANK", 0)))
    parser.add_argument("--master_port", type=int, default=6170)
    parser.add_argument("--log_dir", type=str, default=None)
    parser.add_argument("script")
    parser.add_argument("script_args", nargs=argparse.REMAINDER)
    ns = parser.parse_args(args)

    hosts = ns.ips.split(",")
    master = f"{hosts[0]}:{ns.master_port}"
    procs = start_local_trainers(ns.script, ns.script_args, ns.node_rank,
                                 len(hosts), master, ns.log_dir, hosts=hosts)
    return watch_local_trainers(procs)


def main():
    sys.exit(launch())


if __name__ == "__main__":
    main()
