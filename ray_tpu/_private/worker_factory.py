"""Forkserver-style worker factory: pay the interpreter+import cost once,
fork per worker.

TPU-native analog of the reference worker-pool prestart path (ref:
src/ray/raylet/worker_pool.h PopWorker/PrestartWorkers — the reference
amortizes worker startup by keeping warm processes; here the whole warm
interpreter is amortized). A cold `python -m ray_tpu._private.worker_main`
costs ~0.7 s of imports per worker; at envelope depth (1k+ live actors on a
host, release/benchmarks/README.md:10) that is the difference between
seconds and tens of minutes. The factory imports the full worker stack
once, then serves fork requests over a unix socket at ~10 ms each, with
copy-on-write sharing of the imported interpreter between workers.

Protocol (newline-delimited JSON over a unix stream socket):
    -> {"cmd": "spawn", "log_path": "...", "env": {k: v|null, ...}}
    <- {"pid": 1234} | {"error": "..."}
    -> {"cmd": "ping"}            <- {"ok": true}
    -> {"cmd": "exit"}            (factory exits; forked workers survive)

The factory is strictly single-threaded — forking a multithreaded process
can deadlock the child on locks held by threads that do not survive the
fork, so no event loop, thread pool, or background thread may start before
fork time. The forked child resets per-process state (config cache, RNG)
and runs ``worker_main.main()`` exactly as a cold-started worker would.
"""

from __future__ import annotations

import json
import os
import random
import socket
import sys
import traceback


def _reap() -> None:
    """Collect exited workers (they are this process's children)."""
    while True:
        try:
            pid, _ = os.waitpid(-1, os.WNOHANG)
        except ChildProcessError:
            return
        if pid == 0:
            return


def _fork_worker(req: dict, listener: socket.socket,
                 conn: socket.socket) -> int:
    pid = os.fork()
    if pid:
        return pid
    # ---- child: become a fresh worker process ----
    code = 1
    try:
        os.setsid()  # detach: factory exit must not signal workers
        listener.close()
        conn.close()
        log_path = req.get("log_path")
        if log_path:
            fd = os.open(log_path, os.O_WRONLY | os.O_CREAT | os.O_APPEND,
                         0o644)
            os.dup2(fd, 1)
            os.dup2(fd, 2)
            os.close(fd)
        for key, value in (req.get("env") or {}).items():
            if value is None:
                os.environ.pop(key, None)
            else:
                os.environ[key] = str(value)
        # sys.path was fixed at FACTORY interpreter start; the spawn's
        # PYTHONPATH (driver sys.path additions, runtime-env py_modules/
        # working_dir) must reach this worker's import system or its
        # tasks fail on driver-local modules a cold-started worker would
        # see. Prepend missing entries, preserving their order.
        pythonpath = os.environ.get("PYTHONPATH", "")
        have = set(sys.path)
        for i, entry in enumerate(p for p in pythonpath.split(os.pathsep)
                                  if p and p not in have):
            sys.path.insert(i, entry)
        # the factory's cached config snapshotted ITS env, not this
        # worker's; and forked children share the parent's Mersenne
        # state — identical "random" streams across the pool otherwise
        from .config import reset_global_config

        reset_global_config()
        random.seed(os.urandom(16))
        from . import worker_main

        worker_main.main()
        code = 0
    except BaseException:
        traceback.print_exc()
    finally:
        # never unwind into factory code (atexit hooks, finally blocks of
        # the accept loop) from a forked child
        os._exit(code)


def _serve_conn(conn: socket.socket, listener: socket.socket) -> bool:
    """Handle requests from one raylet connection until EOF.
    Returns False when the factory should exit.

    The raylet connection is persistent, so this loop — not the accept
    loop — is where the factory spends its life; zombie reaping and the
    orphan check must run here too (idle periods after worker churn
    would otherwise accumulate exited children indefinitely). Framing is
    buffered by hand: a stdlib BufferedReader would hide bytes from
    select() and peek() can block, so select-then-recv is the only
    combination that is both line-complete and idle-interruptible."""
    import select

    buf = bytearray()
    try:
        while True:
            line_end = buf.find(b"\n")
            if line_end < 0:
                ready, _, _ = select.select([conn], [], [], 1.0)
                if not ready:
                    _reap()
                    if os.getppid() == 1:
                        return False  # raylet process died without "exit"
                    continue
                chunk = conn.recv(65536)
                if not chunk:
                    break  # EOF: raylet closed the connection
                buf += chunk
                continue
            line = bytes(buf[:line_end]).strip()
            del buf[:line_end + 1]
            if not line:
                continue
            try:
                req = json.loads(line)
            except ValueError:
                break  # corrupt stream: drop the connection
            cmd = req.get("cmd")
            if cmd == "spawn":
                try:
                    reply = {"pid": _fork_worker(req, listener, conn)}
                except OSError as e:
                    reply = {"error": f"fork failed: {e}"}
            elif cmd == "ping":
                reply = {"ok": True}
            elif cmd == "exit":
                return False
            else:
                reply = {"error": f"unknown cmd: {cmd!r}"}
            conn.sendall(json.dumps(reply).encode() + b"\n")
            _reap()
    except (BrokenPipeError, ConnectionResetError, OSError):
        pass
    finally:
        try:
            conn.close()
        except Exception:
            pass
    return True


def main() -> None:
    sock_path = os.environ["RAY_TPU_FACTORY_SOCKET"]
    # Pay the full worker import bill now, before binding: a connectable
    # socket is the readiness signal, so every fork after it is warm.
    from . import worker_main  # noqa: F401

    if "jax" in sys.modules:
        # every forked worker would inherit this process's jax state, and
        # one of them will be leased a chip (device_plane.claim_chips
        # needs a process that has not imported jax yet)
        raise RuntimeError("the worker factory must not import jax")

    try:
        os.unlink(sock_path)
    except FileNotFoundError:
        pass
    listener = socket.socket(socket.AF_UNIX, socket.SOCK_STREAM)
    listener.bind(sock_path)
    listener.listen(8)
    listener.settimeout(1.0)
    try:
        while True:
            _reap()
            # orphaned (raylet process died without "exit"): quit rather
            # than linger as a session leak; forked workers are their own
            # sessions and die through the raylet-connection path instead
            if os.getppid() == 1:
                return
            try:
                conn, _ = listener.accept()
            except socket.timeout:
                continue
            except OSError:
                return
            if not _serve_conn(conn, listener):
                return
    finally:
        listener.close()
        try:
            os.unlink(sock_path)
        except OSError:
            pass


if __name__ == "__main__":
    main()
