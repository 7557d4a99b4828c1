"""Worker process for the `connect_multihost` drill (test_multihost.py).

Run as: python tests/multihost_worker.py <process_id> <coordinator_port>

Each of the 2 workers forces a 2-device CPU backend, joins the
distributed runtime (global mesh = 4 devices across 2 processes), drives
a ShardedKV through insert/get/delete, and checks the results against
the host-computed ground truth. Exit code 0 = all assertions held.
The drill is the DCN analog of the reference's multi-node deployment
(`script.sh:3-41`): one logical server spanning processes.
"""

import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=2 "
    + os.environ.get("XLA_FLAGS", "").replace(
        "--xla_force_host_platform_device_count=8", ""
    )
)


def main() -> int:
    pid = int(sys.argv[1])
    port = sys.argv[2]

    import jax

    # pin the CPU BEFORE any backend init: a child of the test process
    # must never reach for the chip (bench/common.pin_cpu)
    jax.config.update("jax_platforms", "cpu")

    import numpy as np

    from pmdfc_tpu.config import IndexConfig, IndexKind, KVConfig
    from pmdfc_tpu.parallel.shard import (
        ShardedKV,
        connect_multihost,
        make_mesh,
    )
    from pmdfc_tpu.utils.keys import pack_key

    ndev = connect_multihost(f"localhost:{port}", 2, pid)
    assert ndev == 4, f"global device count {ndev} != 4"

    cfg = KVConfig(
        index=IndexConfig(kind=IndexKind.LINEAR, capacity=1 << 14),
        bloom=None,
        paged=False,
    )
    kv = ShardedKV(cfg, mesh=make_mesh(), dispatch="a2a")

    n = 4096
    lo = np.arange(n, dtype=np.uint32)
    keys = np.asarray(pack_key(np.full_like(lo, 3), lo))
    vals = np.stack([lo ^ np.uint32(0x5A5A), lo], axis=-1)

    res = kv.insert(keys, vals)
    assert not res.dropped.any(), "fill-phase insert dropped keys"

    got, found = kv.get(keys)
    assert found.all(), f"{(~found).sum()} inserted keys not found"
    np.testing.assert_array_equal(got, vals)

    hit = kv.delete(keys[: n // 4])
    assert hit.all(), "delete missed inserted keys"
    got2, found2 = kv.get(keys)
    assert not found2[: n // 4].any(), "deleted keys still served"
    assert found2[n // 4 :].all(), "delete clobbered live keys"

    s = kv.stats()
    assert s["puts"] == n and s["gets"] == 2 * n, s
    util = kv.utilization()
    assert 0.0 < util < 1.0, util

    rep = kv.shard_report()
    assert rep["n_shards"] == 4
    assert sum(rep["occupancy"]) == n - n // 4, rep["occupancy"]

    # extent verbs through the replicated body (the one op that needs
    # uncommitted host inputs on a multi-process mesh)
    ek = np.asarray(pack_key(np.uint32(9), np.uint32(1 << 20)))
    _, uncovered = kv.insert_extent(ek, np.asarray([7, 7], np.uint32), 5)
    assert uncovered == 0, uncovered
    eks = np.stack([ek + np.asarray([0, i], np.uint32) for i in range(5)])
    evals, efound = kv.get_extent(eks)
    assert efound.all(), efound

    print(f"worker {pid}: OK (devices={ndev}, util={util:.3f})")
    return 0


if __name__ == "__main__":
    sys.exit(main())
