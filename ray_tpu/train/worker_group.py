"""Gang-scheduled training worker group.

Reference analog: train/v2/_internal/execution/worker_group/worker_group.py:103
(start/poll_status:424/shutdown over one-actor-per-accelerator), rebuilt on
the TPU process model: ONE worker per HOST (jax is multi-controller — each
host process owns all its local chips), gang-reserved through a placement
group so a partial gang never runs (SPMD collectives compiled for a fixed
mesh cannot tolerate missing ranks, SURVEY §7.1 point 3).
"""

from __future__ import annotations

import os
import socket
import threading
import traceback
from typing import Any, Dict, List, Optional

import cloudpickle

from .config import ScalingConfig
from .session import TrainContext, _init_session, _shutdown_session
from ._checkpoint import Checkpoint


class TrainWorker:
    """Actor hosting one rank of the gang (module-level so any worker
    process can deserialize it by import)."""

    def __init__(self, rank: int, experiment_name: str):
        self.rank = rank
        self.experiment_name = experiment_name
        self._thread: Optional[threading.Thread] = None
        self._session = None
        self._error: Optional[str] = None
        self._finished = False

    def node_info(self) -> Dict[str, Any]:
        return {
            "rank": self.rank,
            "node_id": os.environ.get("RAY_TPU_NODE_ID", ""),
            "hostname": socket.gethostname(),
            "pid": os.getpid(),
        }

    def pick_port(self) -> int:
        """A free TCP port on this host (rank 0: jax.distributed coordinator)."""
        with socket.socket() as s:
            s.bind(("", 0))
            return s.getsockname()[1]

    def start(self, train_fn_blob: bytes, train_config: Optional[dict],
              world_size: int, coordinator_address: str,
              restore_path: Optional[str],
              restore_blob: Optional[bytes] = None,
              use_tpu: bool = False,
              start_step: int = 0) -> bool:
        """Install the session and launch the user function on a thread
        (ref: worker_group/thread_runner.py — the train_fn must not block
        the actor, which keeps serving poll()/shutdown()). ``restore_blob``
        carries the checkpoint as a tar when the controller's filesystem is
        not visible from this host; a local ``restore_path`` is used
        directly when it is. ``start_step`` is the controller's persisted
        high-water step: sessions number their steps past it so the GCS
        goodput ledger can classify post-restore replay as rework."""
        import time as _time

        restored = None
        restore_t0 = _time.time()
        restore_bytes = 0
        if restore_blob is not None:
            # the blob is ground truth from the controller — a same-named
            # local directory could be stale state from a previous run
            from ._checkpoint import unpack_blob

            restore_bytes = len(restore_blob)
            restored = Checkpoint(unpack_blob(restore_blob))
        elif restore_path and os.path.isdir(restore_path):
            restored = Checkpoint(restore_path)
        if restored is not None:
            self._observe_restore(_time.time() - restore_t0, restore_bytes)
        context = TrainContext(
            world_size=world_size,
            rank=self.rank,
            node_rank=self.rank,
            experiment_name=self.experiment_name,
            coordinator_address=coordinator_address,
            restored_checkpoint=restored,
            start_step=start_step,
        )
        self._session = _init_session(context)
        self._maybe_init_jax_distributed(context, use_tpu)
        # a restarted gang's train step is byte-identical to the one the
        # dead gang compiled: the fresh processes must find it on disk
        # instead of re-running XLA (SURVEY §7.4 fast gang restart)
        from .._private import device_plane

        device_plane.enable_compilation_cache()
        train_fn = cloudpickle.loads(train_fn_blob)

        def _run():
            try:
                import inspect

                # train_fn may take (config) or nothing (ref: train v2
                # construct_train_func signature handling)
                if inspect.signature(train_fn).parameters:
                    train_fn(train_config if train_config is not None else {})
                else:
                    train_fn()
                # last-step metrics (train_step_seconds et al) would die
                # with this process otherwise: the controller kills the
                # gang as soon as poll() sees "finished", which races the
                # 2s flusher tick — so flush BEFORE flipping _finished
                self._flush_metrics()
                self._finished = True
            except BaseException:  # noqa: BLE001 — reported via poll
                self._error = traceback.format_exc()
                self._flush_metrics()

        self._thread = threading.Thread(target=_run, daemon=True,
                                        name=f"train_fn_rank{self.rank}")
        self._thread.start()
        return True

    @staticmethod
    def _flush_metrics() -> None:
        """Force-ship this process's metric deltas to the GCS now."""
        try:
            from ..util import metrics as m

            m._flush_once(force=True)
        except Exception:  # graftlint: ignore[swallow] — best-effort
            pass  # final flush; the run's result does not depend on it

    def _observe_restore(self, seconds: float, nbytes: int) -> None:
        """train_checkpoint_restore_seconds + bytes: the restore leg of
        gang-restart latency (the save leg rides the session)."""
        try:
            from ..util import metrics as m

            m.Histogram(
                "train_checkpoint_restore_seconds",
                "checkpoint restore/unpack on gang (re)start",
                boundaries=m.TRAIN_STEP_BUCKETS, tag_keys=("job",)
            ).observe(seconds, tags={"job": self.experiment_name})
            if nbytes > 0:
                m.Counter(
                    "train_checkpoint_restore_bytes_total",
                    "bytes unpacked by checkpoint restores",
                    tag_keys=("job",)
                ).inc(nbytes, tags={"job": self.experiment_name})
        except Exception:  # graftlint: ignore[swallow] — telemetry
            pass  # must never fail a gang start

    def _maybe_init_jax_distributed(self, context: TrainContext,
                                    use_tpu: bool) -> None:
        """Multi-host SPMD bring-up (the NCCL-rendezvous analog, ref:
        train/torch/config.py:66 _setup_torch_process_group → here
        jax.distributed over the gang's rank-0 coordinator). A
        ``use_tpu`` rank must run under a lease that holds chips: that
        lease, not this flag, is what took the raylet's CPU pin off this
        process (device_plane.claim_chips, at actor creation)."""
        if not use_tpu:
            return
        from .. import get_tpu_chip_ids
        from .._private import device_plane

        chip_ids = get_tpu_chip_ids()
        if not chip_ids:
            raise RuntimeError(
                "use_tpu train worker holds no TPU chips: its "
                "resources_per_worker must request \"TPU\"")
        device_plane.claim_chips(chip_ids)   # raises if jax beat us here
        if context.world_size <= 1 or not context.coordinator_address:
            return
        try:
            import jax

            jax.distributed.initialize(
                coordinator_address=context.coordinator_address,
                num_processes=context.world_size,
                process_id=context.rank,
            )
        except RuntimeError as e:
            # only "already initialized" (gang restart landed on a reused
            # process) is benign; real rendezvous failures must surface —
            # a silent process-local device view would make the SPMD
            # train_fn fail far from the root cause
            if "already" not in str(e).lower():
                raise

    def poll(self) -> Dict[str, Any]:
        """Status + reports since the last poll (ref: worker_group.py:424
        poll_status). Checkpoints are handed over as paths; the controller
        owns registration/retention (cross-filesystem transfer goes through
        pack_checkpoint)."""
        new_reports = []
        if self._session is not None:
            for rep in self._session.drain():
                new_reports.append({
                    "metrics": rep.metrics,
                    "checkpoint_path": rep.checkpoint.path if rep.checkpoint else None,
                    "step": rep.step,
                    "telemetry": rep.telemetry,
                })
        if self._error is not None:
            status = "errored"
        elif self._finished:
            status = "finished"
        elif self._thread is not None:
            status = "running"
        else:
            status = "idle"
        return {"rank": self.rank, "status": status, "error": self._error,
                "node_id": os.environ.get("RAY_TPU_NODE_ID", ""),
                "reports": new_reports}

    def pack_checkpoint(self, path: str) -> bytes:
        """Tar a reported checkpoint directory for a controller on another
        filesystem."""
        from ._checkpoint import pack_dir

        return pack_dir(path)

    def shutdown(self) -> bool:
        _shutdown_session()
        return True


class WorkerGroup:
    """Create/poll/tear down one gang of TrainWorker actors inside a
    placement group."""

    def __init__(self, scaling: ScalingConfig, experiment_name: str):
        from .. import nodes

        self.scaling = scaling
        self.experiment_name = experiment_name
        host_chips = 0.0
        if scaling.use_tpu:
            host_chips = max((n["Resources"].get("TPU", 0.0)
                              for n in nodes() if n["Alive"]), default=0.0)
        # raises for a use_tpu bundle no host can ever grant
        self.bundle = scaling.worker_resources(host_chips)
        self.pg = None
        self.workers: List[Any] = []
        self.coordinator_address = ""

    def start(self) -> None:
        from .. import remote
        from ..util import placement_group, PlacementGroupSchedulingStrategy

        n = self.scaling.num_workers
        bundle = self.bundle
        self.pg = placement_group([dict(bundle) for _ in range(n)],
                                  strategy=self.scaling.placement_strategy)
        if not self.pg.wait(timeout_seconds=120):
            raise TimeoutError(
                f"placement group for {n} x {bundle} not schedulable")
        actor_cls = remote(TrainWorker)
        self.workers = [
            actor_cls.options(
                resources={k: v for k, v in bundle.items() if k != "CPU"},
                num_cpus=bundle.get("CPU", 1.0),
                max_restarts=0,
                scheduling_strategy=PlacementGroupSchedulingStrategy(
                    placement_group=self.pg,
                    placement_group_bundle_index=i),
            ).remote(i, self.experiment_name)
            for i in range(n)
        ]

    def gang_info(self) -> List[Dict[str, Any]]:
        from .. import get

        return get([w.node_info.remote() for w in self.workers], timeout=120)

    def start_training(self, train_fn, train_config: Optional[dict],
                       restore_path: Optional[str],
                       start_step: int = 0) -> None:
        from .. import get

        infos = self.gang_info()
        if self.scaling.num_workers > 1:
            port = get(self.workers[0].pick_port.remote(), timeout=60)
            self.coordinator_address = f"{infos[0]['hostname']}:{port}"
        # checkpoint for workers on OTHER nodes rides as a tar blob; workers
        # sharing this node's filesystem read the path directly (no n-fold
        # copy of a multi-GB checkpoint through the object store)
        local_node = self._local_node_id()
        restore_blob = None
        remote_ranks = {i for i, inf in enumerate(infos)
                        if inf["node_id"] != local_node}
        if restore_path and os.path.isdir(restore_path) and remote_ranks:
            from ._checkpoint import pack_dir

            restore_blob = pack_dir(restore_path)
        blob = cloudpickle.dumps(train_fn)
        get([
            w.start.remote(blob, train_config, self.scaling.num_workers,
                           self.coordinator_address, restore_path,
                           restore_blob if i in remote_ranks else None,
                           self.scaling.use_tpu, start_step)
            for i, w in enumerate(self.workers)
        ], timeout=300)

    @staticmethod
    def _local_node_id() -> str:
        from .. import _worker_api

        node = _worker_api.node()
        if node is not None:
            return node.node_id.hex()
        return os.environ.get("RAY_TPU_NODE_ID", "")

    def poll(self) -> List[Dict[str, Any]]:
        """One poll round; a dead or unresponsive worker surfaces as
        status='dead'. All ranks are polled concurrently — one hung worker
        must not stall failure detection on the others."""
        from .. import get
        from .. import exceptions as exc

        refs = [w.poll.remote() for w in self.workers]
        out = []
        for i, ref in enumerate(refs):
            try:
                out.append(get(ref, timeout=60))
            except (exc.ActorDiedError, exc.WorkerCrashedError,
                    exc.TaskError, exc.GetTimeoutError) as e:
                out.append({"rank": i, "status": "dead", "error": str(e),
                            "reports": []})
        return out

    def fetch_checkpoint_blob(self, rank: int, path: str) -> Optional[bytes]:
        from .. import get

        try:
            return get(self.workers[rank].pack_checkpoint.remote(path),
                       timeout=120)
        except Exception:
            return None  # worker died before handing the checkpoint over

    def shutdown(self) -> None:
        from .. import kill
        from ..util import remove_placement_group

        for worker in self.workers:
            try:
                kill(worker)
            except Exception:
                pass
        self.workers = []
        if self.pg is not None:
            try:
                remove_placement_group(self.pg)
            except Exception:
                pass
            self.pg = None
