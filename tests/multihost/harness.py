"""Spawn-based multi-process test harness for ``jax.distributed`` on CPU.

CI has no real multi-host cluster, so the multi-host fleet layer is
exercised by SPAWNING N fresh Python processes on one machine: each
worker calls ``jax.distributed.initialize(coordinator, num_processes=N,
process_id=i)`` against a loopback coordinator (process 0 hosts it),
runs the caller's function, and ships its picklable result back over a
pipe.  ``spawn`` (never fork) because jax must be imported/initialized
from scratch in every worker — the pytest parent already holds an
initialized single-process backend.

Failure semantics (what the meta-tests pin):
  * a worker exception (including AssertionError) is re-raised in the
    parent as ``WorkerFailed`` carrying the worker's full traceback,
  * a worker that dies without reporting (os._exit, crash) raises
    ``WorkerFailed`` with its exit code,
  * on timeout every worker is terminated, then killed, then REAPED
    (join) before ``MultihostTimeout`` is raised — no zombie workers
    and the coordinator port is free again for the next run.

Debuggability: with ``REPRO_MH_LOG_DIR`` set, every worker redirects
its stdout/stderr (fd-level, so jax/absl C++ logging is captured too)
to ``$REPRO_MH_LOG_DIR/worker-<i>.log`` and appends its traceback there
on failure — CI uploads the directory as an artifact when the
multihost job fails, so coordinator hangs and harness timeouts leave
per-worker evidence behind.
"""
from __future__ import annotations

import multiprocessing as mp
import os
import socket
import sys
import time
import traceback


class MultihostTimeout(RuntimeError):
    """The run exceeded its deadline; all workers were killed+reaped."""

    def __init__(self, msg, pids=()):
        super().__init__(msg)
        self.pids = tuple(pids)


class WorkerFailed(RuntimeError):
    """A worker raised (or died); carries its traceback / exit code."""

    def __init__(self, process_id: int, detail: str):
        super().__init__(f"multihost worker {process_id} failed:\n"
                         f"{detail}")
        self.process_id = process_id
        self.detail = detail


def free_port() -> int:
    """An OS-assigned free TCP port on loopback (bind-0 then release)."""
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def port_is_free(port: int) -> bool:
    """True when a listener can bind the port (post-timeout hygiene)."""
    with socket.socket() as s:
        s.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        try:
            s.bind(("127.0.0.1", port))
            return True
        except OSError:
            return False


def _exit_barrier(n: int, timeout_ms: int = 5000):
    """Best-effort exit alignment so no worker's process disappears
    while a peer still talks to the coordination service.  NOT
    ``jax.distributed.shutdown()``: the client's error-polling thread
    races service teardown (a peer's disconnect surfaces as a fatal
    "another task died"), so workers align here and then ``os._exit``
    without any teardown at all."""
    if n <= 1:
        return
    try:
        from jax._src import distributed
        client = distributed.global_state.client
        if client is not None:
            client.wait_at_barrier("harness/exit", timeout_ms)
    except Exception:
        pass


def _redirect_to_log(i: int):
    """fd-level stdout/stderr redirection into the harness log dir
    (no-op unless ``REPRO_MH_LOG_DIR`` is set).  Line-buffered text on
    a dup2'd fd: C++-side logging lands in the same file, and the
    ``os._exit`` exit path loses at most the current line."""
    log_dir = os.environ.get("REPRO_MH_LOG_DIR")
    if not log_dir:
        return False
    os.makedirs(log_dir, exist_ok=True)
    # APPEND: several run_multihost calls share one log dir in a CI
    # job, and the run that matters for the artifact is usually an
    # EARLIER failing one — truncating would ship the last test's logs
    f = open(os.path.join(log_dir, f"worker-{i}.log"), "a", buffering=1)
    os.dup2(f.fileno(), 1)
    os.dup2(f.fileno(), 2)
    sys.stdout = os.fdopen(1, "w", buffering=1, closefd=False)
    sys.stderr = os.fdopen(2, "w", buffering=1, closefd=False)
    print(f"[multihost harness] ---- worker {i} pid {os.getpid()} "
          f"(new run) ----")
    return True


def _worker(fn, args, i: int, n: int, port: int, conn):
    """Worker bootstrap: fresh jax + distributed init, then run fn."""
    logged = False
    try:
        logged = _redirect_to_log(i)
        # the workers test KV-store coordination, not an accelerator:
        # pinned to the CPU so none of them tries to claim a chip
        os.environ["JAX_PLATFORMS"] = "cpu"
        import jax
        jax.distributed.initialize(f"127.0.0.1:{port}", num_processes=n,
                                   process_id=i,
                                   initialization_timeout=60)
        result = fn(*args)
        conn.send(("ok", result))
        conn.close()
        _exit_barrier(n)
        os._exit(0)
    except BaseException:
        if logged:
            traceback.print_exc()       # keep a copy in the worker log
        try:
            conn.send(("error", traceback.format_exc()))
            conn.close()
        except Exception:
            pass
        _exit_barrier(n)
        os._exit(1)


def _reap(procs):
    """Terminate, then kill, then JOIN every worker (no zombies)."""
    for p in procs:
        if p.is_alive():
            p.terminate()
    for p in procs:
        p.join(5)
        if p.is_alive():
            p.kill()
            p.join(5)


def run_multihost(fn, n_procs: int, *, args=(), timeout: float = 300.0,
                  env=None, port: int = None) -> list:
    """Run ``fn(*args)`` in ``n_procs`` spawned jax.distributed workers.

    ``fn`` must be a module-level (picklable) function; inside it jax is
    initialized, so ``jax.process_index()/process_count()`` and
    ``CoordinatorCollectives.from_jax()`` work.  Returns the per-worker
    results in process-id order.  ``env`` overrides environment
    variables for the workers (set in the parent around the spawn, so
    they land before the child's interpreter starts); ``port`` pins the
    coordinator port (default: an OS-assigned free one).
    """
    ctx = mp.get_context("spawn")
    if port is None:
        port = free_port()
    overrides = {**(env or {}), "JAX_PLATFORMS": "cpu"}
    saved = {k: os.environ.get(k) for k in overrides}
    os.environ.update(overrides)
    procs, conns = [], []
    try:
        child_ends = []
        for i in range(n_procs):
            recv_end, send_end = ctx.Pipe(duplex=False)
            conns.append(recv_end)
            child_ends.append(send_end)
            procs.append(ctx.Process(
                target=_worker, args=(fn, tuple(args), i, n_procs, port,
                                      send_end),
                daemon=True, name=f"mh-worker-{i}"))
        for p in procs:
            p.start()
        for c in child_ends:
            c.close()               # parent copy: lets EOF surface
        deadline = time.monotonic() + timeout
        results = [None] * n_procs
        got = [False] * n_procs
        while not all(got):
            progressed = False
            for i, c in enumerate(conns):
                if not got[i] and c.poll(0):
                    try:
                        results[i] = c.recv()
                    except EOFError:
                        results[i] = (
                            "error",
                            f"worker exited (code {procs[i].exitcode}) "
                            f"without reporting a result")
                    got[i] = True
                    progressed = True
            if all(got):
                break
            if all(not p.is_alive() for p in procs):
                for i in range(n_procs):
                    if not got[i]:
                        try:
                            if conns[i].poll(0.2):
                                results[i] = conns[i].recv()
                            else:
                                raise EOFError
                        except EOFError:
                            results[i] = (
                                "error",
                                f"worker exited (code "
                                f"{procs[i].exitcode}) without "
                                f"reporting a result")
                        got[i] = True
                break
            if time.monotonic() > deadline:
                pids = [p.pid for p in procs]
                _reap(procs)
                raise MultihostTimeout(
                    f"multihost run ({n_procs} workers, port {port}) "
                    f"timed out after {timeout:.0f}s; workers killed "
                    f"and reaped", pids=pids)
            if not progressed:
                time.sleep(0.02)
        for p in procs:
            p.join(10)
        _reap(procs)
        # exit codes matter only for workers that never reported: a
        # worker that delivered its result and then lost the teardown
        # race with the coordination service already did its job.
        # Prefer an error that carries a traceback — a peer that died
        # from the coordinator's "task died" cascade is the victim,
        # not the cause.
        errors = [(i, payload) for i, (status, payload)
                  in enumerate(results) if status == "error"]
        if errors:
            with_tb = [e for e in errors if "Traceback" in e[1]]
            i, payload = (with_tb or errors)[0]
            raise WorkerFailed(i, payload)
        return [payload for _, payload in results]
    finally:
        _reap(procs)
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v
