"""SPMD function executor — the paper's MPI-function-executor, TPU-native.

The paper's executor launches one persistent MPI world, then carves
Intra-communicators so many heterogeneous MPI Python functions run
concurrently.  Here the persistent world is the pilot's device set; an
"Intra-communicator" is a sub-mesh carved from it; collectives inside task
functions are ``jax.lax`` ops under ``shard_map``.

The paper's §V-A performance lesson — *build the communicator once, reuse
it, cache it* — is structural here: sub-meshes are cached by their devices
and shape, and specialized callables (one ``jax.jit`` wrapper binding the
function to its sub-mesh) by (function, sub-mesh).  The key holds no
abstract inputs: a cached wrapper meets a new input shape by retracing and
compiling inside ``jax.jit``'s own cache.  The ``cache=False`` mode exists
only for the Exp-1 ablation that reproduces the paper's cold-communicator
overhead.

``stats``: ``specializations`` counts wrappers made, ``cache_hits`` wrappers
reused, and ``compiles`` the XLA backend compiles and persistent-cache
loads (``jax.monitoring``'s backend-compile event) that happened on a
thread inside ``execute``: a new shape counts 1 or more, a repeat 0.

On the CPU, slots may outnumber real devices: slot blocks then map onto
the available devices (dedup'd), preserving scheduling semantics while
executing on what exists.  On an accelerator a slot is a chip, and a slot
id beyond the device count is an error, never a silent time-share.
"""
from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
from jax._src.dispatch import BACKEND_COMPILE_EVENT

from ..sharding.partition import make_mesh
from . import tracing
from .futures import ResourceSpec, TaskRecord

# the executor whose ``execute`` runs on this thread, for the compile count
_executing = threading.local()
_listener_lock = threading.Lock()
_listening = False


def _on_monitoring_event(event: str, duration: float, **_):
    ex = getattr(_executing, "executor", None)
    if ex is not None and event == BACKEND_COMPILE_EVENT:
        with ex._lock:
            ex.stats["compiles"] += 1


def _listen_for_compiles():
    """Register the one process-wide compile listener, once."""
    global _listening
    with _listener_lock:
        if not _listening:
            jax.monitoring.register_event_duration_secs_listener(
                _on_monitoring_event)
            _listening = True


class SPMDFunctionExecutor:
    def __init__(self, devices=None, cache: bool = True):
        self.devices = list(devices if devices is not None else jax.devices())
        self.cache_enabled = cache
        self._mesh_cache: Dict[Tuple, Any] = {}
        self._call_cache: Dict[Tuple, Callable] = {}
        self._lock = threading.Lock()
        self.stats = {"compiles": 0, "specializations": 0, "cache_hits": 0}
        _listen_for_compiles()

    # ----------------------------- sub-mesh ----------------------------- #
    def submesh(self, slot_ids: Tuple[int, ...],
                mesh_shape: Optional[Tuple[int, int]] = None):
        """Carve the sub-mesh ('Intra-communicator') for a slot block."""
        nreal = len(self.devices)
        if self.devices[0].platform != "cpu" and max(slot_ids) >= nreal:
            raise ValueError(
                f"slots {tuple(slot_ids)} exceed the {nreal} "
                f"{self.devices[0].platform} devices of this pilot")
        devs = []
        seen = set()
        for s in slot_ids:
            d = self.devices[s % nreal]
            if id(d) not in seen:
                seen.add(id(d))
                devs.append(d)
        n = len(devs)
        if mesh_shape and mesh_shape[0] * mesh_shape[1] <= n:
            shape = mesh_shape
        else:
            shape = (n, 1)
        key = (tuple(d.id for d in devs[: shape[0] * shape[1]]), shape)
        with self._lock:
            if self.cache_enabled and key in self._mesh_cache:
                return self._mesh_cache[key]
        mesh = make_mesh(shape, devices=devs[: shape[0] * shape[1]])
        with self._lock:
            if self.cache_enabled:
                self._mesh_cache[key] = mesh
        return mesh

    # ----------------------------- dispatch ----------------------------- #
    def _specialize(self, fn: Callable, mesh, jit: bool):
        """One compiled callable per (fn, mesh) — the communicator cache."""
        key = (id(fn), tuple(d.id for d in mesh.devices.flat),
               mesh.shape_tuple)
        with self._lock:
            if self.cache_enabled and key in self._call_cache:
                self.stats["cache_hits"] += 1
                return self._call_cache[key]
        if jit:
            wrapped = jax.jit(lambda *a, **kw: fn(mesh, *a, **kw))
        else:
            wrapped = lambda *a, **kw: fn(mesh, *a, **kw)  # noqa: E731
        with self._lock:
            # double-checked: a concurrent miss may have registered first —
            # reuse its callable so both share one compiled executable
            if self.cache_enabled and key in self._call_cache:
                self.stats["cache_hits"] += 1
                return self._call_cache[key]
            self.stats["specializations"] += 1
            if self.cache_enabled:
                self._call_cache[key] = wrapped
        return wrapped

    def execute(self, task: TaskRecord) -> Any:
        """Run a task body on its allocated slots.  Blocking; called from an
        agent worker thread (the MPI-Worker analog)."""
        kwargs = dict(task.kwargs)
        jit = kwargs.pop("_jit", True)
        if task.ckpt_ctx is not None:
            # checkpointable body: inject the live Checkpoint context.
            # The context is not traceable, so the wrapper-level jit is
            # skipped — step bodies manage their own jit.
            kwargs["ckpt"] = task.ckpt_ctx
            jit = False
        outer = getattr(_executing, "executor", None)
        _executing.executor = self
        try:
            with tracing.span("rp.executor.dispatch", task.uid,
                              slots=task.slot_ids):
                if task.kind == "spmd":
                    mesh = self.submesh(task.slot_ids,
                                        task.resources.mesh_shape)
                    call = self._specialize(task.fn, mesh, jit)
                    out = call(*task.args, **kwargs)
                else:  # plain python / bash-wrapped function: single slot
                    out = task.fn(*task.args, **kwargs)
            if _has_arrays(out):
                with tracing.span("rp.executor.device_wait", task.uid,
                                  slots=task.slot_ids):
                    out = jax.block_until_ready(out)
            return out
        finally:
            _executing.executor = outer


def _has_arrays(x) -> bool:
    return any(isinstance(l, jax.Array) for l in jax.tree.leaves(x))
