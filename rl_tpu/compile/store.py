"""Persistent executable store: serialized XLA executables keyed by
program signature, so a restarted worker *loads* instead of recompiles.

Two cache layers exist and they solve different problems:

- JAX's persistent **compilation cache** (``jax_compilation_cache_dir``,
  enabled by default via :func:`rl_tpu.config.enable_compile_cache`)
  caches the XLA *backend compile* keyed by optimized HLO. It still pays
  tracing + lowering on every process start, and its key is only
  computable *after* lowering.
- This **executable store** serializes the loaded executable itself
  (:mod:`jax.experimental.serialize_executable` —
  ``serialize``/``deserialize_and_load``) under a key computed purely
  from the *abstract call signature* (program name, arg
  shapes/dtypes/sharding spec, donation, backend, jax version). Because
  the key needs no tracing, a warm restart skips ``jit.lower()``
  entirely — which is where most cold-start time goes once the XLA
  cache is warm.

The key deliberately hashes the *registration-time* signature rather
than the jaxpr: two programs registered under the same name with the
same avals but different Python closures would collide, so the registry
includes a caller-supplied ``fingerprint`` (the closure's configuration)
in the key, and the store adds a hash of the ``rl_tpu`` sources so an
executable never outlives the code it was built from.
Feature detection is per call — ``serialize`` raises on backends/
executables that don't support it, and every failure degrades to the
lower+compile path, never to an error.

Layout on disk: one ``<sha256>.jexec`` zlib-compressed pickle per
executable — ``(header_dict, payload, in_tree, out_tree)`` — plus a sibling
``.json`` header for ``ls``-ability. Writes are atomic (tmp + rename)
so concurrent fleet members racing on the same key are safe: last
writer wins with identical content.
"""

from __future__ import annotations

import functools
import hashlib
import json
import os
import pickle
import tempfile
import threading
import time
import zlib
from typing import Any

__all__ = [
    "ExecutableStore",
    "abstract_like",
    "default_store",
    "set_default_store",
    "signature_of",
]

_ENV_DIR = "RL_TPU_EXEC_STORE_DIR"
_ENV_DISABLE = "RL_TPU_NO_EXEC_STORE"
_SUFFIX = ".jexec"


@functools.cache
def _code_version() -> str:
    """Hash of the ``rl_tpu`` sources. The signature key below never sees
    the traced function, so without this an executable built from an
    older checkout would load under the same key once the cache directory
    outlives the code (``JAX_COMPILATION_CACHE_DIR`` on a shared machine;
    parent and change measured in one call) — and run the old program."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    h = hashlib.sha256()
    for dirpath, dirnames, filenames in os.walk(root):
        dirnames.sort()
        for fn in sorted(filenames):
            if fn.endswith(".py"):
                path = os.path.join(dirpath, fn)
                h.update(os.path.relpath(path, root).encode())
                with open(path, "rb") as f:
                    h.update(f.read())
    return h.hexdigest()


def _sharding_sig(sh: Any) -> str:
    """Normalize a leaf sharding for keying: default single-device
    placement reads as "" so a concrete array and the abstract
    ``ShapeDtypeStruct`` (sharding None) that describes it produce the
    SAME key — warm restarts build keys from abstract signatures."""
    if sh is None:
        return ""
    try:
        from jax.sharding import NamedSharding, SingleDeviceSharding

        if isinstance(sh, SingleDeviceSharding):
            return ""
        if isinstance(sh, NamedSharding):
            return f"NS({sorted(sh.mesh.shape.items())},{sh.spec})"
    except Exception:
        pass
    return repr(sh)


def abstract_like(tree: Any) -> Any:
    """Map a pytree of concrete arrays to ``ShapeDtypeStruct`` avals for
    AOT signatures. ``NamedSharding``s are preserved (an FSDP program's
    key must carry its layout); single-device placement is dropped so
    the aval keys identically to a hand-built abstract signature."""
    import jax
    from jax.sharding import NamedSharding

    def one(x):
        sh = getattr(x, "sharding", None)
        sh = sh if isinstance(sh, NamedSharding) else None
        return jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=sh)

    return jax.tree.map(one, tree)


def signature_of(tree: Any) -> str:
    """Deterministic string signature of a pytree of arrays /
    ``ShapeDtypeStruct``s: tree structure + per-leaf shape/dtype/sharding.

    Computable from abstract avals alone — no tracing, no lowering —
    which is what lets a warm restart skip ``lower()`` entirely.
    """
    import jax

    leaves, treedef = jax.tree_util.tree_flatten(tree)
    parts = [str(treedef)]
    for leaf in leaves:
        shape = tuple(getattr(leaf, "shape", ()))
        dtype = str(getattr(leaf, "dtype", type(leaf).__name__))
        sh = getattr(leaf, "sharding", None)
        parts.append(f"{shape}:{dtype}:{_sharding_sig(sh)}")
    return "|".join(parts)


class ExecutableStore:
    """sha-keyed persistent store of serialized XLA executables.

    ``root=None`` resolves ``$RL_TPU_EXEC_STORE_DIR`` then
    ``executables/`` under :func:`rl_tpu.config.compile_cache_dir` (so
    ``JAX_COMPILATION_CACHE_DIR`` places both layers);
    ``$RL_TPU_NO_EXEC_STORE=1``
    disables persistence (the in-memory layer still works, so duplicate
    programs within one process — e.g. N identical fleet engines —
    still compile once).
    """

    def __init__(self, root: str | None = None, *, memory_cache: bool = True):
        if root is None:
            from ..config import compile_cache_dir

            root = os.environ.get(_ENV_DIR) or os.path.join(
                compile_cache_dir(), "executables"
            )
        self.root = root
        self.disabled = os.environ.get(_ENV_DISABLE, "") not in ("", "0")
        self._lock = threading.Lock()
        self._mem: dict[str, Any] | None = {} if memory_cache else None
        self.stats = {"hits": 0, "misses": 0, "saves": 0, "errors": 0, "mem_hits": 0}

    # -- keys -----------------------------------------------------------
    def key_for(
        self,
        name: str,
        args: Any,
        *,
        backend: str | None = None,
        fingerprint: str = "",
        extra: str = "",
    ) -> str:
        """Content key from the abstract call signature (never lowers)."""
        import jax

        if backend is None:
            backend = jax.default_backend()
        h = hashlib.sha256()
        for part in (
            "rl_tpu.exec.v2",
            _code_version(),
            jax.__version__,
            backend,
            name,
            fingerprint,
            extra,
            signature_of(args),
        ):
            h.update(part.encode())
            h.update(b"\0")
        return h.hexdigest()

    # -- paths ----------------------------------------------------------
    def _path(self, key: str) -> str:
        return os.path.join(self.root, key + _SUFFIX)

    def has(self, key: str) -> bool:
        if self._mem is not None and key in self._mem:
            return True
        return not self.disabled and os.path.exists(self._path(key))

    def keys(self) -> list[str]:
        try:
            return sorted(
                f[: -len(_SUFFIX)]
                for f in os.listdir(self.root)
                if f.endswith(_SUFFIX)
            )
        except OSError:
            return []

    def evict(self, key: str) -> None:
        """Drop one entry everywhere (memory + disk); used when a loaded
        executable fails its first call (stale/foreign entry)."""
        with self._lock:
            if self._mem is not None:
                self._mem.pop(key, None)
        for p in (self._path(key), self._path(key)[: -len(_SUFFIX)] + ".json"):
            try:
                os.remove(p)
            except OSError:
                pass

    def clear(self) -> None:
        with self._lock:
            if self._mem is not None:
                self._mem.clear()
        for key in self.keys():
            for p in (self._path(key), self._path(key)[: -len(_SUFFIX)] + ".json"):
                try:
                    os.remove(p)
                except OSError:
                    pass

    # -- save/load ------------------------------------------------------
    def save(self, key: str, compiled: Any, *, meta: dict | None = None) -> bool:
        """Serialize ``compiled`` under ``key``. Returns False (never
        raises) when the backend/executable doesn't support serialization."""
        if self._mem is not None:
            with self._lock:
                self._mem[key] = compiled
        if self.disabled:
            return False
        from jax.experimental import serialize_executable

        try:
            payload, in_tree, out_tree = serialize_executable.serialize(compiled)
            header = {
                "version": 1,
                "key": key,
                "created": time.time(),
                **(meta or {}),
            }
            # compressed: a serialized TPU executable is several times
            # the size of jax's own (compressed) cache entry for the same
            # program — 490 MB against 60 MB for chip_smoke.py's thirty
            # programs — and both live under one size-limited directory
            blob = zlib.compress(
                pickle.dumps((header, payload, in_tree, out_tree), protocol=4), 3
            )
        except Exception:
            with self._lock:
                self.stats["errors"] += 1
            return False
        try:
            os.makedirs(self.root, exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=self.root, suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(blob)
                os.replace(tmp, self._path(key))
            finally:
                if os.path.exists(tmp):
                    os.remove(tmp)
            with open(self._path(key)[: -len(_SUFFIX)] + ".json", "w") as f:
                json.dump({**header, "bytes": len(blob)}, f)
        except OSError:
            with self._lock:
                self.stats["errors"] += 1
            return False
        with self._lock:
            self.stats["saves"] += 1
        return True

    def load(self, key: str) -> Any | None:
        """Deserialize the executable stored under ``key``, or None on
        miss / unsupported / corrupt entry (corrupt entries are evicted)."""
        if self._mem is not None:
            with self._lock:
                hit = self._mem.get(key)
            if hit is not None:
                with self._lock:
                    self.stats["mem_hits"] += 1
                return hit
        if self.disabled:
            return None
        path = self._path(key)
        if not os.path.exists(path):
            with self._lock:
                self.stats["misses"] += 1
            return None
        from jax.experimental import serialize_executable

        try:
            with open(path, "rb") as f:
                header, payload, in_tree, out_tree = pickle.loads(
                    zlib.decompress(f.read())
                )
            compiled = serialize_executable.deserialize_and_load(
                payload, in_tree, out_tree
            )
        except Exception:
            # a corrupt/incompatible entry must not wedge startup: evict
            # it so the compile path rebuilds and overwrites.
            with self._lock:
                self.stats["errors"] += 1
            try:
                os.remove(path)
            except OSError:
                pass
            return None
        if self._mem is not None:
            with self._lock:
                self._mem[key] = compiled
        with self._lock:
            self.stats["hits"] += 1
        return compiled


_default: ExecutableStore | None = None
_default_lock = threading.Lock()


def default_store() -> ExecutableStore:
    """Process-default store (what registered programs use unless a
    store is passed explicitly)."""
    global _default
    with _default_lock:
        if _default is None:
            _default = ExecutableStore()
        return _default


def set_default_store(store: ExecutableStore | None) -> ExecutableStore | None:
    """Swap the process default (tests isolate themselves with a tmpdir
    store); returns the previous one so callers can restore it."""
    global _default
    with _default_lock:
        prev = _default
        _default = store
        return prev
