"""Async parameter-server training: native C++ table server + two worker
processes updating a shared sparse embedding table."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import subprocess

import numpy as np

import paddle_tpu.distributed.fleet as fleet

WORKER = '''
import os, sys
import numpy as np
from paddle_tpu.distributed.ps import PSClient
wid = int(sys.argv[1])
c = PSClient(os.environ["PADDLE_PSERVERS_IP_PORT_LIST"])
rng = np.random.default_rng(wid)
targets = np.random.default_rng(0).normal(size=(32, 8)).astype(np.float32)
for _ in range(200):
    ids = rng.integers(0, 32, 8)
    w = c.pull_sparse(0, ids, dim=8)
    c.push_sparse(0, ids, w - targets[ids], lr=0.1)   # dL/dw of ||w-t||^2/2
c.barrier(world=2)
c.close()
'''


def main():
    srv = fleet.init_server()
    print("server on", srv.endpoint)
    c = fleet.ps_client()
    c.create_sparse_table(0, dim=8)

    procs = [subprocess.Popen([sys.executable, "-c", WORKER, str(i)],
                              env=dict(os.environ)) for i in range(2)]
    for p in procs:
        assert p.wait(timeout=120) == 0

    targets = np.random.default_rng(0).normal(size=(32, 8)).astype(np.float32)
    final = c.pull_sparse(0, np.arange(32), dim=8)
    print("max |w - target| after async training:",
          float(np.abs(final - targets).max()))
    fleet.stop_worker()
    srv.stop()




def main_sharded():
    """Same async-SGD loop across a 2-server FLEET: sparse rows
    key-shard (k % 2), each server holds only its half, and the client
    heartbeats both (kill one and the next verb raises a clean
    PSServerDownError naming the endpoint)."""
    from paddle_tpu.distributed.ps import PSClient, PSServer

    servers, c = [], None
    try:
        for _ in range(2):
            servers.append(PSServer())
        c = PSClient([s.endpoint for s in servers])
        c.create_sparse_table(0, dim=8)
        targets = np.random.default_rng(0).normal(
            size=(32, 8)).astype(np.float32)
        rng = np.random.default_rng(7)
        for _ in range(200):
            ids = rng.integers(0, 32, 8)
            w = c.pull_sparse(0, ids, dim=8)
            c.push_sparse(0, ids, w - targets[ids], lr=0.1)
        final = c.pull_sparse(0, np.arange(32), dim=8)
        print("sharded fleet: max |w - target| =",
              float(np.abs(final - targets).max()),
              "| alive servers:", c.alive())
    finally:
        if c is not None:
            c.close()
        for s in servers:
            s.stop()


if __name__ == "__main__":
    main()
    main_sharded()
