"""One accelerator chip per worker process.

A TPU chip belongs to one process at a time, and a process that has
initialized JAX's TPU backend holds every chip it can see. So the
cluster parent stays off the accelerator, and each worker is handed
exactly one chip through its environment before it imports JAX. On a
CPU-only host (or with ``JAX_PLATFORMS`` naming no TPU) workers need no
chip and none is assigned.
"""
from __future__ import annotations

import contextlib
import multiprocessing as mp
import os
import queue
import socket
import sys
import time
from typing import Any, Callable, Dict, Iterator, Optional


def tpu_chip_count() -> int:
    """TPU chips this host's workers may use, counted from the PCI bus —
    without initializing a JAX backend. 0 when ``JAX_PLATFORMS`` rules
    the TPU out."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return 0
    from jax._src import hardware_utils
    return hardware_utils.num_available_tpu_chips_and_device_id()[0]


def check_parent_off_chip() -> None:
    """Raise if this (parent) process holds an accelerator backend: its
    workers could then not open their chips, and would fail or hang."""
    if "jax" not in sys.modules:
        return
    from jax._src import xla_bridge
    if not xla_bridge.backends_are_initialized():
        return
    import jax
    backend = jax.default_backend()
    if backend != "cpu":
        raise RuntimeError(
            f"the cluster parent initialized JAX's {backend!r} backend and "
            f"holds its devices; the workers need them. Keep the parent off "
            f"JAX (run any single-process work in a child process).")


def _free_port() -> int:
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("localhost", 0))
        return s.getsockname()[1]


def worker_env(chip: int) -> Dict[str, str]:
    """Environment that confines one worker's TPU runtime to ``chip``.
    Each worker runs a one-chip topology of its own with its own runtime
    port. libtpu's host-wide load lock would let only the first worker
    in; the disjoint ``TPU_VISIBLE_CHIPS`` is what keeps the workers off
    each other's chips, so that lock is lifted for the worker alone."""
    port = _free_port()
    return {"TPU_VISIBLE_CHIPS": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_PORT": str(port),
            "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
            "CLOUD_TPU_TASK_ID": "0",
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}


@contextlib.contextmanager
def environ(extra: Dict[str, str]) -> Iterator[None]:
    """Temporarily extend ``os.environ`` (a spawned child copies it)."""
    saved = {k: os.environ.get(k) for k in extra}
    os.environ.update(extra)
    try:
        yield
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def describe_device() -> Dict[str, Any]:
    """The device this (worker) process computes on, as JAX reports it."""
    import jax
    devs = jax.devices()
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "id": d.id,
            "count": len(devs),
            "coords": list(getattr(d, "coords", []) or []),
            "chip": os.environ.get("TPU_VISIBLE_CHIPS", "")}


def run_in_child(target: Callable, *args, env: Optional[Dict[str, str]] = None,
                 timeout_s: float = 1800.0) -> Any:
    """Run ``target(*args, out)`` in a spawned child process and return
    the one result it puts on queue ``out``. The child has exited when
    this returns, so whatever device it held is free again."""
    ctx = mp.get_context("spawn")
    out = ctx.Queue()
    proc = ctx.Process(target=target, args=args + (out,))
    with environ(env or {}):
        proc.start()
    deadline = time.monotonic() + timeout_s
    got, have = None, False
    while not have and time.monotonic() < deadline \
            and (proc.is_alive() or not out.empty()):
        try:
            got, have = out.get(timeout=1.0), True
        except queue.Empty:
            pass
    proc.join(timeout=60.0)
    if proc.is_alive():
        proc.kill()
        proc.join()
    if not have or proc.exitcode != 0:
        raise RuntimeError(f"child {getattr(target, '__name__', target)} "
                           f"exited with code {proc.exitcode}")
    return got
