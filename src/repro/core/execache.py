"""AOT executable cache: trace + lower + compile once per (fn, shapes).

The persistent XLA compilation cache (`repro.util.enable_compilation_cache`)
only skips the *backend compile* — its key is computed from the lowered
StableHLO module, so a fresh process still pays full jaxpr tracing and
MLIR lowering for every program in the prover (the dominant cost: the
pipeline is hundreds of small programs, not one big one).  This module
removes that cost end to end:

* first call per shape signature: ``jax.jit(fn).lower(*args).compile()``
  (ahead-of-time), the resulting ``Compiled`` goes into a process-wide
  registry and is serialized to disk via
  ``jax.experimental.serialize_executable``;
* later calls in the same process hit the registry (no dispatch-time
  cache probing beyond one dict lookup);
* a FRESH process deserializes the executable directly — no trace, no
  lower, no XLA compile.

Conventions for wrapped functions: dynamic arguments are positional jax
arrays, static arguments are keywords (listed in ``static_argnames``).
The cache key is (name, backend, device kind, dynamic shapes/dtypes,
statics); the proof geometry — graph spec, quantization, aggregation
window T — is fully encoded in the argument shapes, so `ProvingKey`s for
different configs can never collide in the cache.  The disk directory
sits under the shared compile-cache root (`repro.util.cache_root`,
overridable by ``$ZKDL_EXEC_CACHE``) and is keyed by jax/jaxlib version
+ backend + device kind (entries from another version or another chip
generation are never loaded), and every load failure falls back to a
fresh compile.

Counters (`stats()`) make warm starts auditable: a warmed process
reports ``misses == 0`` — the cross-process "never re-traces" contract
pinned by tests/test_exec_cache.py.
"""
from __future__ import annotations

import functools
import hashlib
import os
import pickle
import re
import threading

_DISK_ENV = "ZKDL_EXEC_CACHE"          # path override; "off"/"0" disables disk
_MODE_ENV = "ZKDL_EXEC_MODE"           # "off" disables the whole cache
_SCHEMA = 2                            # bump to invalidate old disk entries

_lock = threading.RLock()
_registry: dict = {}
_stats = {"hits": 0, "misses": 0, "disk_hits": 0, "disk_writes": 0,
          "disk_corrupt": 0}


def enabled() -> bool:
    return os.environ.get(_MODE_ENV, "on").lower() not in ("off", "0")


def stats() -> dict:
    with _lock:
        return dict(_stats)


def reset_stats() -> None:
    with _lock:
        for k in _stats:
            _stats[k] = 0


def clear() -> None:
    """Drop the in-process registry (disk entries stay)."""
    with _lock:
        _registry.clear()


def disk_root() -> str | None:
    """Root of the disk cache, above its version subdirectory:
    ``$ZKDL_EXEC_CACHE`` when set (``off``/``0``/``none`` = disk
    disabled, returns None), else ``zkdl-exec/`` under the shared
    compile-cache root."""
    d = os.environ.get(_DISK_ENV, "")
    if d.lower() in ("off", "0", "none"):
        return None
    if d:
        return d
    from repro.util import cache_root
    return os.path.join(cache_root(), "zkdl-exec")


@functools.cache
def device_kind() -> str:
    """``device_kind`` of the default device (e.g. ``TPU v5 lite``)."""
    import jax
    return jax.devices()[0].device_kind


def cache_dir() -> str | None:
    """Disk directory for serialized executables (None = disk disabled)."""
    d = disk_root()
    if d is None:
        return None
    import jax
    import jaxlib
    kind = re.sub(r"[^A-Za-z0-9.]+", "_", device_kind())
    sub = (f"{jax.__version__}-{jaxlib.__version__}-"
           f"{jax.default_backend()}-{kind}-v{_SCHEMA}")
    return os.path.join(d, sub)


def _argsig(a):
    return (tuple(a.shape), str(a.dtype))


def _key(name: str, args, statics, pos_statics=()):
    import jax
    return (name, jax.default_backend(), device_kind(),
            tuple(sorted(statics.items())), repr(pos_statics),
            tuple(_argsig(a) for a in args))


def _disk_path(key) -> str | None:
    base = cache_dir()
    if base is None:
        return None
    h = hashlib.sha256(repr(key).encode()).hexdigest()
    return os.path.join(base, f"{h}.exe.pkl")


def _load_or_compile(key, jitted, args, statics):
    path = _disk_path(key)
    if path is not None and os.path.exists(path):
        try:
            with open(path, "rb") as f:
                _stored_key, payload, in_tree, out_tree = pickle.load(f)
            import jax
            from jax.experimental import serialize_executable as se
            # a wrapped program is a plain single-device jit; loaded for
            # every local device (the default), it would expect one shard
            # per device in a process with several (forced host devices)
            comp = se.deserialize_and_load(
                payload, in_tree, out_tree,
                execution_devices=jax.devices()[:1])
            with _lock:
                _registry[key] = comp
                _stats["disk_hits"] += 1
            return comp
        except Exception:
            # stale/truncated/corrupt/foreign entry: a MISS, never an
            # error.  Count it, drop the bad file (so a crashed write or
            # bit rot can't be retried forever), recompile + rewrite.
            with _lock:
                _stats["disk_corrupt"] += 1
            try:
                os.remove(path)
            except OSError:
                pass
    # Compile with the XLA persistent cache OFF: an executable that came
    # out of that cache re-serializes WITHOUT its object-code symbols
    # (loads fine in-process, "Symbols not found" in any other process).
    # Only a genuine backend compile yields a portable serialization —
    # and this cache subsumes the persistent cache for wrapped programs
    # anyway (it also skips trace + lower, which the XLA cache cannot).
    # The use-the-cache decision is memoized process-wide on the first
    # compile (`compilation_cache.is_cache_used`), so flipping the
    # config flag alone is a no-op: reset the memo around the flip.
    import jax
    from jax._src import compilation_cache as _cc
    prev = jax.config.jax_enable_compilation_cache
    try:
        jax.config.update("jax_enable_compilation_cache", False)
        _cc.reset_cache()
        comp = jitted.lower(*args, **statics).compile()
    finally:
        jax.config.update("jax_enable_compilation_cache", prev)
        _cc.reset_cache()
    with _lock:
        _registry[key] = comp
        _stats["misses"] += 1
    if path is not None:
        try:
            from jax.experimental import serialize_executable as se
            payload, in_tree, out_tree = se.serialize(comp)
            os.makedirs(os.path.dirname(path), exist_ok=True)
            tmp = f"{path}.tmp.{os.getpid()}"
            with open(tmp, "wb") as f:
                # the key rides along so diagnostics (and bulk preloads)
                # can map a file back to its program
                pickle.dump((repr(key), payload, in_tree, out_tree), f)
            os.replace(tmp, path)
            with _lock:
                _stats["disk_writes"] += 1
        except Exception:
            pass  # serialization unsupported on this backend: memory-only
    return comp


def wrap(name: str, fn, static_argnames=(), static_argnums=()):
    """Wrap ``fn`` (pure traced jax code) in the executable cache.

    Returns a callable with the convention: dynamic args positional,
    statics keyword-only — except positions in ``static_argnums``, which
    carry hashable statics with a deterministic ``repr`` (e.g. the
    frozen-dataclass ``FieldSpec``: the field primitives take the spec
    positionally at hundreds of call sites).  With the cache disabled
    (ZKDL_EXEC_MODE=off) or a non-array dynamic argument, falls back to
    plain ``jax.jit``.
    """
    import jax
    nums = tuple(static_argnums)
    jitted = jax.jit(fn, static_argnames=tuple(static_argnames),
                     static_argnums=nums or None)

    def call(*args, **statics):
        if nums:
            pos_statics = tuple(args[i] for i in nums)
            dyn = tuple(a for i, a in enumerate(args) if i not in nums)
        else:
            pos_statics, dyn = (), args
        # nested use (this body traced inside another wrapped/jitted
        # program) must inline: a Compiled can't consume tracers
        if (not enabled()
                or any(isinstance(a, jax.core.Tracer) for a in dyn)
                or any(not hasattr(a, "shape") for a in dyn)):
            return jitted(*args, **statics)
        key = _key(name, dyn, statics, pos_statics)
        with _lock:
            comp = _registry.get(key)
        if comp is not None:
            with _lock:
                _stats["hits"] += 1
        else:
            comp = _load_or_compile(key, jitted, args, statics)
        try:
            return comp(*dyn)
        except TypeError:
            # aval mismatch the (shape, dtype) key can't see (weak types,
            # committed devices): correctness first, plain jit fallback
            return jitted(*args, **statics)

    call.__name__ = name
    call._jitted = jitted       # escape hatch (tests, parity oracles)
    return call
