"""Node: process/session bring-up for head and worker nodes.

TPU-native analog of the reference launcher (ref: python/ray/_private/node.py,
services.py — spawns gcs_server/raylet binaries). Here the GCS and raylet are
asyncio servers hosted on a dedicated IO thread inside the head process;
their socket-based contracts are identical whether they live in-process or as
separate daemons, which is what lets the native (C++) substrate replace them
under the same wire protocol in later milestones.
"""

from __future__ import annotations

import atexit
import os
import shutil
import time
import uuid
from typing import Dict, Optional

from . import device_plane
from .config import global_config
from .gcs import GcsServer
from .ids import NodeID
from .object_store import SharedObjectStore
from .raylet import Raylet
from .rpc import EventLoopThread

from .config import TEMP_ROOT as _TEMP_ROOT


def default_resources() -> Dict[str, float]:
    res = {"CPU": float(os.cpu_count() or 1)}
    res["memory"] = float(os.sysconf("SC_PAGE_SIZE") * os.sysconf("SC_PHYS_PAGES"))
    # TPU detection: count local TPU chips without initializing the runtime
    # for CPU-only runs (ref: _private/accelerators/tpu.py:109).
    num_tpus = _detect_tpu_chips()
    if num_tpus:
        res["TPU"] = float(num_tpus)
    return res


def _detect_tpu_chips() -> int:
    """Local TPU chips, counted from device nodes so the driver never
    initialises jax (that would take the chip from the worker that is
    leased it). Hosts with the accel driver expose one ``/dev/accelN``
    per chip; VFIO hosts expose one numbered IOMMU group per chip under
    ``/dev/vfio`` next to the ``vfio`` control node, which is not a chip.
    The TPU_* topology variables are no guide: a one-chip machine cut
    from a four-chip host still exports the host's 2x2 bounds."""
    if os.environ.get("RAY_TPU_FAKE_CHIPS"):
        return int(os.environ["RAY_TPU_FAKE_CHIPS"])
    return (_count_numbered("/dev", "accel")
            or _count_numbered("/dev/vfio", ""))


def _count_numbered(directory: str, prefix: str) -> int:
    try:
        names = os.listdir(directory)
    except OSError:
        return 0
    return sum(1 for n in names
               if n.startswith(prefix) and n[len(prefix):].isdigit())


def _detect_accelerator_type() -> str:
    """TPU generation label from the VM metadata env TPU runtimes set
    (ref: accelerators/tpu.py get_current_node_accelerator_type —
    there read from instance metadata; queued-resources/GKE export it
    as TPU_ACCELERATOR_TYPE, e.g. 'v5litepod-8'). Values align with
    ray_tpu.util.accelerators constants; tasks target them via
    ``@remote(accelerator_type=...)``."""
    acc = (os.environ.get("TPU_ACCELERATOR_TYPE")
           or os.environ.get("ACCELERATOR_TYPE", ""))
    if not acc:
        return ""
    gen = acc.split("-")[0].lower()
    mapping = {"v2": "TPU-V2", "v3": "TPU-V3", "v4": "TPU-V4",
               "v5litepod": "TPU-V5LITE", "v5e": "TPU-V5LITE",
               "v5p": "TPU-V5P", "v6e": "TPU-V6E"}
    # unknown generations publish NOTHING: fabricating "TPU-NVIDIA" from
    # a GPU VM's ACCELERATOR_TYPE would pollute the label namespace
    return mapping.get(gen, "")


class Node:
    """A head (GCS + raylet) or worker (raylet only) node."""

    def __init__(
        self,
        head: bool,
        session_name: Optional[str] = None,
        gcs_address: Optional[str] = None,
        resources: Optional[Dict[str, float]] = None,
        labels: Optional[Dict[str, str]] = None,
        io: Optional[EventLoopThread] = None,
        object_store_memory: Optional[int] = None,
        port: Optional[int] = None,
        node_ip: Optional[str] = None,
        external_store_address: Optional[str] = None,
    ):
        """``port``: bind the head GCS on TCP (0 = ephemeral) so worker nodes
        on other hosts can join over DCN; default is a unix socket
        (single-host). ``node_ip``: the routable IP this node advertises to
        peers (TCP binds listen on 0.0.0.0); defaults to loopback, which is
        correct for single-host test clusters only."""
        self.head = head
        cfg = global_config()
        if head:
            self.session_name = session_name or (
                f"rtpu_{time.strftime('%Y%m%d_%H%M%S')}_{os.getpid()}_{uuid.uuid4().hex[:6]}"
            )
        else:
            assert session_name and gcs_address, "worker nodes need a session + GCS"
            self.session_name = session_name
        self.session_dir = os.path.join(_TEMP_ROOT, self.session_name)
        os.makedirs(self.session_dir, exist_ok=True)
        self.node_id = NodeID.from_random()
        self.node_ip = node_ip or "127.0.0.1"
        if gcs_address:
            self.gcs_address = gcs_address
        elif port is not None:
            self.gcs_address = f"0.0.0.0:{port}"  # advertised via node_ip
        else:
            self.gcs_address = os.path.join(self.session_dir, "gcs.sock")
        tcp_mode = port is not None or (gcs_address and "/" not in gcs_address)
        if tcp_mode:
            self.raylet_address = "0.0.0.0:0"     # ephemeral, all interfaces
        else:
            self.raylet_address = os.path.join(
                self.session_dir, f"raylet_{self.node_id.hex()[:12]}.sock")
        self.io = io or EventLoopThread(name="ray_tpu_node")
        self._owns_io = io is None

        # Each node owns a distinct store namespace; cross-node access rides
        # the raylet pull path (a same-host shortcut would mask transfer bugs
        # in the multi-node test harness, ref: cluster_utils.py:135).
        self.store = SharedObjectStore(
            os.path.join(self.session_name, f"node_{self.node_id.hex()[:12]}"),
            object_store_memory or cfg.object_store_memory_bytes,
        )
        self.gcs_server: Optional[GcsServer] = None
        if head:
            # journal in the session dir: a restarted GCS rebuilds its
            # actor/PG/job/KV tables from it (the Redis-persistence analog)
            self.gcs_server = GcsServer(
                self.gcs_address,
                journal_path=os.path.join(self.session_dir, "gcs_journal.bin"),
                advertise_host=self.node_ip,
                # external kv_server (the Redis role): head-disk loss
                # becomes survivable — a new head re-seeds from it
                external_store_address=external_store_address)
        node_labels = dict(labels or {})
        acc_type = _detect_accelerator_type()
        if acc_type and "accelerator_type" not in node_labels:
            node_labels["accelerator_type"] = acc_type
        # whether this node's workers may take a TPU at all: deployments
        # that want a chip per replica ask here (llm/serve.py)
        node_labels.setdefault(device_plane.JAX_PLATFORMS_LABEL,
                               device_plane.node_jax_platforms())
        self.raylet = Raylet(
            node_id=self.node_id,
            session_name=self.session_name,
            socket_path=self.raylet_address,
            gcs_address=self.gcs_address,
            resources=resources or default_resources(),
            store=self.store,
            labels=node_labels,
            advertise_host=self.node_ip,
        )
        self._started = False

    def start(self):
        async def _start():
            if self.gcs_server is not None:
                await self.gcs_server.start()
                self.gcs_address = self.gcs_server.server.address
                self.raylet.gcs_address = self.gcs_address
                # remote joiners (CLI worker nodes) fetch the session
                # name through the KV instead of a side channel
                self.gcs_server.storage.put(
                    "cluster", "session_name", self.session_name.encode())
            await self.raylet.start()
            self.raylet_address = self.raylet.server.address

        self.io.run(_start(), timeout=30)
        self._started = True
        atexit.register(self.stop)

    def stop(self):
        if not self._started:
            return
        self._started = False
        try:
            async def _stop():
                await self.raylet.stop()
                if self.gcs_server is not None:
                    await self.gcs_server.stop()

            self.io.run(_stop(), timeout=10)
        except Exception:
            pass
        if self._owns_io:
            self.io.stop()
        self.store.destroy()
        if self.head:
            # whole-session cleanup: worker nodes' store namespaces too
            shutil.rmtree(os.path.join("/dev/shm", self.session_name),
                          ignore_errors=True)
            shutil.rmtree(self.session_dir, ignore_errors=True)

    def die(self):
        """Abrupt node death (fault injection): kill workers + drop
        connections; no graceful unregister, no store cleanup."""
        if not self._started:
            return
        self._started = False
        try:
            self.io.run(self.raylet.die(), timeout=10)
        except Exception:
            pass
        if self._owns_io:
            self.io.stop()
