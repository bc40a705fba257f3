"""AOT executable (de)serialization — the payload inside an artifact.

``jax.jit(...).lower(*args).compile()`` yields a ``Compiled`` stage;
``jax.experimental.serialize_executable`` turns it into bytes plus the
arg/result pytree structures, and loading the bytes back gives a
callable that runs WITHOUT tracing or XLA compilation — a deserialized
call emits zero ``Compiling`` log lines, which is what lets
``compile_watch()`` assert a 0-compile warm rollout (the fleet-scope
R2 budget).

Where executable serialization is infeasible (an exotic backend, a
jaxlib without PJRT SerializeExecutable), ``serialize_compiled``
raises and the caller degrades to the persistent compilation cache
(artifacts/cache.py) — warm starts stay bounded-time, just not
zero-log. ``jax.export`` (StableHLO) is deliberately NOT used as the
payload: it skips retracing but still pays XLA compilation at load,
which the fingerprint-checked executable path exists to avoid.
"""

from __future__ import annotations

import pickle
from typing import Callable

__all__ = ["serialize_compiled", "load_compiled", "compile_aot"]

#: pickle protocol pinned so artifacts written by newer interpreters
#: stay loadable by the fleet's oldest supported python
_PICKLE_PROTO = 4


def compile_aot(jitted, *args):
    """Eagerly lower + compile a ``jax.jit`` wrapper for exactly these
    argument shapes/dtypes — the ``Compiled`` both the in-process
    cache and the store persist. Donation declared on the wrapper is
    preserved through lowering."""
    import jax
    specs = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), args)
    return jitted.lower(*specs).compile()


def serialize_compiled(compiled) -> bytes:
    """``Compiled`` -> artifact payload bytes. Raises on backends that
    cannot serialize executables (callers journal and fall back). The
    ids of the devices it was compiled for ride with the payload: a
    loaded executable must be told them, or it wants one shard per
    local device."""
    from jax.experimental import serialize_executable as se
    payload, in_tree, out_tree = se.serialize(compiled)
    device_ids = [d.id for d in
                  compiled._executable._unloaded_executable.device_list]
    return pickle.dumps((payload, in_tree, out_tree, device_ids),
                        protocol=_PICKLE_PROTO)


def load_compiled(blob: bytes) -> Callable:
    """Artifact payload bytes -> a loaded executable callable, bound to
    the devices it was compiled for. Raises ValueError on any malformed
    payload (the store's crc catches torn bytes; this catches a valid
    frame around a wrong payload)."""
    import jax
    from jax.experimental import serialize_executable as se
    try:
        payload, in_tree, out_tree, device_ids = pickle.loads(blob)
    except Exception as e:  # noqa: BLE001 — any unpickle defect
        raise ValueError(f"artifact payload does not unpickle: {e}") \
            from e
    by_id = {d.id: d for d in jax.devices()}
    return se.deserialize_and_load(
        payload, in_tree, out_tree,
        execution_devices=[by_id[i] for i in device_ids])
