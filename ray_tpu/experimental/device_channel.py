"""Device-to-device tensor channel over the PJRT transfer fabric.

Reference analog: python/ray/experimental/channel/torch_tensor_nccl_channel.py
— there, compiled-graph device tensors move actor→actor over NCCL p2p.
The TPU-native substrate is `jax.experimental.transfer`: each writer
process runs one PJRT transfer server; `write()` registers device arrays
for pull and publishes (uuid, address, specs) on a tiny shm control
channel; `read()` connects once per peer and pulls the arrays straight
into its own devices. On a TPU pod the bytes ride the runtime's transfer
fabric (ICI/DCN) — no host pickle, no plasma copy. The host-shm tensor
lane (experimental/channel.py) remains the fallback when arrays must
cross into non-jax processes.

Single-writer, single-reader (p2p, like the reference's NCCL channel);
the control channel provides ordering and backpressure (capacity 1
payload in flight until the reader consumes).

Validated: cross-process pulls on the CPU PJRT runtime (the transfer
server needs explicit ``transport_addresses`` — the default empty list
has no data plane and pulls hang). Locally-attached TPU runtimes carry
the same API; not measured on the chip yet.

    ch = DeviceChannel()                    # writer side
    ch.write({"x": jnp_array, "w": other})  # pytree of jax arrays
    ...
    ch = DeviceChannel(path)                # reader side (same path)
    out = ch.read()                         # device arrays, same treedef
"""

from __future__ import annotations

import secrets
import threading
from typing import Any, Dict, Optional

from .channel import Channel, DEFAULT_CAPACITY

# RLock: _connection() -> _transfer_server() nests under the same lock
_server_lock = threading.RLock()
_server = None
_connections: Dict[str, Any] = {}


def _transfer_server():
    """One PJRT transfer server per process (lazy). The bind host must
    be ROUTABLE from the peers (config.device_transfer_host; loopback
    default covers one host, TPU pods set the node IP) and the
    transport_addresses list must be non-empty — with the default empty
    list the server has no data-plane transports and cross-process
    pulls hang forever."""
    global _server
    import jax

    with _server_lock:
        if _server is None:
            from jax.experimental import transfer

            from .._private.config import global_config

            dev = jax.devices()[0]
            host = getattr(global_config(), "device_transfer_host", "") \
                or "127.0.0.1"
            _server = transfer.start_transfer_server(
                dev.client, address=f"{host}:0",
                transport_addresses=[f"{host}:0"])
        return _server


def _connection(address: str):
    with _server_lock:
        conn = _connections.get(address)
        if conn is None:
            conn = _connections[address] = _transfer_server().connect(
                address)
        return conn


class DeviceChannel:
    """One writer, one reader; payloads are pytrees of jax arrays."""

    def __init__(self, path: Optional[str] = None, *,
                 capacity: int = DEFAULT_CAPACITY, create: bool = False):
        # control lane: uuid/address/spec metadata (tiny), plus the
        # channel's ordering + backpressure semantics
        self._control = Channel(path, num_readers=1, capacity=capacity,
                                create=create or path is None)
        self.path = self._control.path

    # --- writer ---

    def write(self, arrays: Any, timeout: Optional[float] = None) -> None:
        import jax

        flat, treedef = jax.tree.flatten(arrays)
        if not flat or not all(isinstance(a, jax.Array) for a in flat):
            # tensor-bearing payloads that aren't PURE jax-array pytrees
            # must NOT silently degrade to host pickling — the whole
            # point of this channel is the device fabric. That includes
            # mixed pytrees (a device array next to a scalar would drag
            # the array through the pickled control lane).
            import numpy as np

            if any(isinstance(a, (jax.Array, np.ndarray)) for a in flat):
                raise TypeError(
                    "DeviceChannel payloads must be pytrees whose "
                    "leaves are ALL jax arrays; split host scalars out, "
                    "or use experimental.channel.Channel for host data")
            # non-tensor payloads (compiled-DAG error markers, small
            # control values) ride the control lane inline
            self._control.write({"inline": arrays}, timeout=timeout)
            return
        server = _transfer_server()
        uid = secrets.randbits(62)
        # metadata publishes FIRST: a control-write timeout then pins
        # nothing (await_pull has no unregister — registering first
        # would leak the device arrays on every failed write). The pull
        # protocol is a rendezvous, so a reader that pulls before the
        # registration below simply blocks until it lands.
        self._control.write({
            "uuid": uid,
            "address": server.address(),
            "specs": [(tuple(a.shape), str(a.dtype)) for a in flat],
            "treedef": treedef,
        }, timeout=timeout)
        server.await_pull(uid, flat)

    def close_write(self) -> None:
        self._control.close_write()

    # --- reader ---

    def read(self, slot: int = 0, timeout: Optional[float] = None) -> Any:
        """``slot`` kept for Channel signature compatibility (compiled
        DAG exec loops call read(slot)); DeviceChannel is 1:1, slot 0."""
        import jax
        import jax.numpy as jnp

        if slot != 0:
            raise ValueError("DeviceChannel is single-reader (slot 0)")
        meta = self._control.read(0, timeout=timeout)
        if "inline" in meta:
            return meta["inline"]
        conn = _connection(meta["address"])
        sharding = jax.sharding.SingleDeviceSharding(jax.devices()[0])
        specs = [jax.ShapeDtypeStruct(shape, jnp.dtype(dtype),
                                      sharding=sharding)
                 for shape, dtype in meta["specs"]]
        flat = conn.pull(meta["uuid"], specs)
        return jax.tree.unflatten(meta["treedef"], flat)

    # --- lifecycle ---

    def close(self) -> None:
        self._control.close()

    def unlink(self) -> None:
        self._control.unlink()

    def __reduce__(self):
        return (DeviceChannel, (self.path,))
