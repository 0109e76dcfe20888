"""Multi-node tests: spillback, inter-node object transfer, node death
(ref: python/ray/tests — the cluster_utils.Cluster-backed distributed suites)."""

import os
import time

import numpy as np
import pytest

import ray_tpu
from ray_tpu.cluster_utils import Cluster
from ray_tpu._private.task_spec import NodeAffinitySchedulingStrategy


@pytest.fixture
def cluster2():
    """Head with 1 CPU + one 4-CPU worker node, driver connected."""
    cluster = Cluster(head_node_args={"resources": {"CPU": 1.0}}, connect=True)
    node2 = cluster.add_node(num_cpus=4)
    yield cluster, node2
    cluster.shutdown()


@ray_tpu.remote
def where_am_i():
    return os.environ["RAY_TPU_NODE_ID"]


def test_spillback_to_second_node(cluster2):
    cluster, node2 = cluster2
    # 4 CPUs can't fit on the 1-CPU head: the lease must spill to node2.
    ref = where_am_i.options(num_cpus=4).remote()
    assert ray_tpu.get(ref, timeout=60) == node2.node_id.hex()


def test_cross_node_object_fetch(cluster2):
    cluster, node2 = cluster2

    @ray_tpu.remote(num_cpus=4)
    def make_array():
        return np.arange(300_000, dtype=np.float32)  # > inline threshold

    ref = make_array.remote()
    out = ray_tpu.get(ref, timeout=60)  # sealed on node2, pulled to head
    np.testing.assert_array_equal(out, np.arange(300_000, dtype=np.float32))


def test_cross_node_arg_transfer(cluster2):
    cluster, node2 = cluster2
    arr = np.random.default_rng(0).standard_normal(200_000).astype(np.float32)
    big = ray_tpu.put(arr)  # sealed in the head node's store

    @ray_tpu.remote(num_cpus=4)
    def total(a):
        return float(a.sum())

    # runs on node2, which must pull the argument from the head node
    assert abs(ray_tpu.get(total.remote(big), timeout=60) - float(arr.sum())) < 1e-2


def test_node_affinity_strategy(cluster2):
    cluster, node2 = cluster2
    strat = NodeAffinitySchedulingStrategy(node_id=node2.node_id.hex(), soft=False)
    ref = where_am_i.options(num_cpus=1, scheduling_strategy=strat).remote()
    assert ray_tpu.get(ref, timeout=60) == node2.node_id.hex()


def test_locality_aware_leasing(cluster2):
    """A DEFAULT-strategy task whose big argument was produced on node2
    leases at node2 (ref: lease_policy.h LocalityAwareLeasePolicy) —
    even though the head raylet has CPU available."""
    cluster, node2 = cluster2

    @ray_tpu.remote(num_cpus=2)
    def make_big():
        return np.zeros(500_000, dtype=np.float32)  # ~2 MB, sealed on node2

    # the 2-CPU request only fits node2 → result lives there
    big = make_big.remote()
    ray_tpu.wait([big], timeout=60)

    @ray_tpu.remote(num_cpus=1)
    def consume(a):
        return os.environ["RAY_TPU_NODE_ID"], float(a[0])

    # head has a free CPU, but the argument bytes are on node2: the
    # locality-aware lease must start (and grant) there
    node, val = ray_tpu.get(consume.remote(big), timeout=60)
    assert node == node2.node_id.hex()
    assert val == 0.0


def test_accelerator_type_scheduling(monkeypatch):
    """@remote(accelerator_type=...) lands on the node publishing that
    generation label (auto-detected from TPU VM metadata env; ref:
    util/accelerators + accelerators/tpu.py)."""
    from ray_tpu.cluster_utils import Cluster
    from ray_tpu.util.accelerators import TPU_V4

    # a TPU host ambiently exports TPU_ACCELERATOR_TYPE for its own
    # chips; clear it so only OUR worker node carries a label
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE", raising=False)
    monkeypatch.delenv("ACCELERATOR_TYPE", raising=False)
    cluster = Cluster(head_node_args={"resources": {"CPU": 2.0}},
                      connect=True)
    monkeypatch.setenv("TPU_ACCELERATOR_TYPE", "v4-8")
    tpu_node = cluster.add_node(num_cpus=2)  # label auto-published
    monkeypatch.delenv("TPU_ACCELERATOR_TYPE")
    try:
        @ray_tpu.remote(num_cpus=1, accelerator_type=TPU_V4)
        def where():
            return os.environ["RAY_TPU_NODE_ID"]

        assert ray_tpu.get(where.remote(), timeout=60) == \
            tpu_node.node_id.hex()
    finally:
        cluster.shutdown()


def test_node_death_loses_objects(cluster2):
    cluster, node2 = cluster2

    # max_retries=0: with retries the object would be recoverable via
    # lineage reconstruction (test_object_lifecycle.py covers that); here we
    # want the unrecoverable-loss path
    @ray_tpu.remote(num_cpus=4, max_retries=0)
    def big_result():
        return np.ones(300_000, dtype=np.float32)

    ref = big_result.remote()
    # Wait for the result to be sealed on node2 WITHOUT pulling it to the
    # head store: poll the GCS object directory.
    core = ray_tpu._worker_api.core()
    deadline = time.time() + 30
    while time.time() < deadline:
        locs = core.io.run(core.gcs.call(
            "get_object_locations", {"object_ids": [ref.id()]}))
        if locs[ref.id()]:
            break
        time.sleep(0.05)
    else:
        pytest.fail("object never sealed on node2")
    cluster.remove_node(node2)  # abrupt death
    with pytest.raises(ray_tpu.exceptions.ObjectLostError):
        ray_tpu.get(ref, timeout=30)


def test_node_death_fails_running_task(cluster2):
    cluster, node2 = cluster2

    @ray_tpu.remote(num_cpus=4, max_retries=0)
    def sleeper():
        time.sleep(60)
        return 1

    ref = sleeper.remote()
    time.sleep(1.0)  # let the lease land on node2
    cluster.remove_node(node2)
    with pytest.raises((ray_tpu.exceptions.WorkerCrashedError,
                        ray_tpu.exceptions.TaskError)):
        ray_tpu.get(ref, timeout=30)


def test_tcp_transport_cluster():
    """Whole control plane on TCP loopback — the DCN cross-host path."""
    cluster = Cluster(head_node_args={"resources": {"CPU": 2.0}},
                      connect=True, tcp=True)
    try:
        assert ":" in cluster.address and "/" not in cluster.address

        @ray_tpu.remote
        def echo(x):
            return x * 2

        assert ray_tpu.get(echo.remote(21), timeout=60) == 42
        node2 = cluster.add_node(num_cpus=4)
        ref = where_am_i.options(num_cpus=4).remote()
        assert ray_tpu.get(ref, timeout=60) == node2.node_id.hex()
    finally:
        cluster.shutdown()
