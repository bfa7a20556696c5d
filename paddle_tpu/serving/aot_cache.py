"""AOT warmup cache: serialized compiled executables for ~instant
replica cold start.

The serving predictor's closed shape menu pays all XLA compile time at
``warmup()`` — fine for the first replica, but a respawned replica under
a traffic spike re-traces the whole (batch x length) bucket cross-product
before it can answer anything. That is TensorFlow's deferred-compilation
tradeoff (PAPERS.md, TF OSDI'16) paid at the worst possible moment: the
fleet is already a replica short.

This module persists each warmed bucket variant as a serialized compiled
executable (``jax.jit(...).lower(feed).compile()`` ->
``jax.experimental.serialize_executable.serialize``), so a fresh replica
deserializes the menu from disk in milliseconds instead of recompiling
it. The cache is strictly an *accelerator*: any miss, version skew, or
corruption falls back to the live trace path with a warning — a broken
cache can cost startup time, never correctness or availability.

Key discipline (one file per executable)::

    <dir>/<model_hash[:16]>-<name>-<bucket_sig>.aot

- ``model_hash`` — the PTM1 payload digest for merged deploy artifacts
  (``trainer/merge_model.py`` writes ``md5(payload)`` into the file), or
  a structural fingerprint (graph topology + param shapes/dtypes, hook
  code hashes) for live (graph, params) pairs. Params are traced
  arguments (graftlint PT201 pins no embedded constants), so the
  compiled program depends on shapes, never values — but the PTM1 key
  is the conservative spec: a new artifact re-traces once.
- ``name`` / ``bucket_sig`` — which executable ("infer", "encode",
  "generate") for which warmed bucket (e.g. ``b4_t32``, plus the pinned
  ``kK_lL`` pair for the search).
- The jax / jaxlib / XLA backend fingerprint is recorded INSIDE the
  entry, not in the filename: a cache written by another jax resolves
  to the same path, is detected as stale at load, warned about, and
  overwritten by the fresh compile — so upgrades self-heal instead of
  leaking orphaned files per version.

Failure handling:

- **miss** (no file): compile live, then :meth:`AOTCache.save`.
- **stale** (env fingerprint mismatch): warn, compile live, overwrite.
- **corrupt** (bad magic / digest mismatch / unpicklable / fails to
  deserialize or execute): QUARANTINE — the entry is renamed to
  ``*.bad`` so it can be inspected but never re-loaded — warn, compile
  live, overwrite. Corruption is never fatal: a replica with a mangled
  cache boots exactly like one with no cache.

Entries verify end-to-end at load: the deserialized executable is run
once against the warmup feed before it is trusted (this also pre-touches
its buffers, so the first real request pays nothing). ``stats`` counts
{hits, misses, stale, quarantined, saved} for ``/healthz`` and the
fleet bench.
"""

from __future__ import annotations

import hashlib
import io
import os
import pickle
from typing import Any, Dict, Optional

from paddle_tpu.utils.log import get_logger

logger = get_logger("serving.aot")

_MAGIC = b"PTAC1"  # paddle_tpu AOT cache, format v1


def env_fingerprint() -> str:
    """jax / jaxlib / XLA backend identity an executable is only valid
    for. Serialized executables are NOT portable across these."""
    import jax
    try:
        import jaxlib
        jaxlib_v = getattr(jaxlib, "__version__", "?")
    except Exception:  # noqa: BLE001 — fingerprint must never raise
        jaxlib_v = "?"
    try:
        from jax.extend import backend as _backend
        plat = _backend.get_backend()
        backend_v = f"{plat.platform}/{plat.platform_version}"
    except Exception:  # noqa: BLE001
        backend_v = "?"
    return f"jax={jax.__version__};jaxlib={jaxlib_v};xla={backend_v}"


def _hash_update_attr(h, value) -> None:
    """Feed one graph attr into the fingerprint. Callables (beam-control
    hooks pinned in the config) hash by their compiled bytecode, so a
    changed hook body invalidates the cache even under the same name."""
    if callable(value):
        code = getattr(value, "__code__", None)
        if code is not None:
            h.update(code.co_code)
            h.update(repr(code.co_consts).encode())
        else:
            h.update(repr(value).encode())
    elif isinstance(value, dict):
        for k in sorted(value, key=str):
            h.update(str(k).encode())
            _hash_update_attr(h, value[k])
    elif isinstance(value, (list, tuple)):
        for v in value:
            _hash_update_attr(h, v)
    else:
        h.update(repr(value).encode())


def model_fingerprint(graph, params: Dict[str, Any]) -> str:
    """Structural hash of (graph topology, param shapes/dtypes) for live
    models that never went through ``--job=merge``. Parameter VALUES are
    excluded on purpose: they are traced arguments, not program
    constants, so two checkpoints of one topology share executables."""
    h = hashlib.sha1()
    for name in sorted(graph.layers):
        ldef = graph.layers[name]
        h.update(name.encode())
        h.update(str(getattr(ldef, "type", "?")).encode())
        _hash_update_attr(h, getattr(ldef, "attrs", {}))
    for name in sorted(params):
        v = params[name]
        h.update(name.encode())
        h.update(str(getattr(v, "shape", None)).encode())
        h.update(str(getattr(v, "dtype", None)).encode())
    return h.hexdigest()


class AOTCache:
    """One directory of serialized executables for one model version.

    ``load`` returns a ready-to-call ``jax.stages.Compiled`` (or None on
    any miss/stale/corrupt outcome — the caller compiles live), ``save``
    persists one. Thread-compatible: serving warms single-threaded; a
    fleet of replicas sharing one directory is safe because writes are
    atomic (tmp + ``os.replace``) and readers verify digests.
    """

    def __init__(self, cache_dir: str, model_hash: str):
        self.dir = cache_dir
        self.model_hash = str(model_hash)
        self.stats = {"hits": 0, "misses": 0, "stale": 0,
                      "quarantined": 0, "saved": 0}
        os.makedirs(self.dir, exist_ok=True)

    # ------------------------------------------------------------ paths
    def path(self, name: str, sig: str) -> str:
        safe = "".join(c if (c.isalnum() or c in "._-") else "_"
                       for c in f"{name}-{sig}")
        return os.path.join(self.dir, f"{self.model_hash[:16]}-{safe}.aot")

    def _quarantine(self, path: str, reason: str):
        self.stats["quarantined"] += 1
        bad = path + ".bad"
        try:
            os.replace(path, bad)
            logger.warning(
                "AOT cache entry %s is corrupt (%s); quarantined to %s "
                "and falling back to live trace", path, reason, bad)
        except OSError as e:
            logger.warning(
                "AOT cache entry %s is corrupt (%s) and could not be "
                "quarantined (%r); falling back to live trace",
                path, reason, e)

    # ------------------------------------------------------------- load
    def load(self, name: str, sig: str, verify_args=None):
        """Deserialize one executable, or None (miss/stale/corrupt — the
        caller must compile live). ``verify_args`` (the warmup call
        args) runs the loaded executable once before it is trusted; a
        mismatched or mangled program quarantines instead of serving."""
        path = self.path(name, sig)
        try:
            with open(path, "rb") as f:
                raw = f.read()
        except FileNotFoundError:
            self.stats["misses"] += 1
            return None
        except OSError as e:
            self.stats["misses"] += 1
            logger.warning("AOT cache read failed for %s (%r); live trace",
                           path, e)
            return None
        if raw[:len(_MAGIC)] != _MAGIC:
            self._quarantine(path, "bad magic")
            return None
        digest, payload = raw[len(_MAGIC):len(_MAGIC) + 16], \
            raw[len(_MAGIC) + 16:]
        if hashlib.md5(payload).digest() != digest:
            self._quarantine(path, "payload digest mismatch")
            return None
        try:
            entry = pickle.loads(payload)
            env, blob = entry["env"], entry["exe"]
            in_tree, out_tree = entry["in_tree"], entry["out_tree"]
            device_ids = entry["devices"]
        except Exception as e:  # noqa: BLE001 — any unpickle failure
            self._quarantine(path, f"unpicklable: {e!r}")
            return None
        if env != env_fingerprint():
            # stale is NOT corruption: the entry was valid for another
            # jax/XLA; warn once per entry and let save() overwrite it
            self.stats["stale"] += 1
            logger.warning(
                "AOT cache entry %s was serialized for %s but this "
                "process runs %s; falling back to live trace (the fresh "
                "compile will overwrite it)", path, env, env_fingerprint())
            return None
        try:
            import jax
            from jax.experimental import serialize_executable as se
            # load onto the devices it was compiled for: jax 0.9.0
            # otherwise spreads it over EVERY device of the backend and
            # a one-device program then demands one shard per device
            by_id = {d.id: d for d in jax.devices()}
            compiled = se.deserialize_and_load(
                blob, in_tree, out_tree,
                execution_devices=[by_id[i] for i in device_ids])
            if verify_args is not None:
                compiled(*verify_args)  # trust only an exe that runs
        except Exception as e:  # noqa: BLE001 — deserialize/exec failure
            self._quarantine(path, f"failed to deserialize/execute: {e!r}")
            return None
        self.stats["hits"] += 1
        return compiled

    # ------------------------------------------------------------- save
    def save(self, name: str, sig: str, compiled) -> bool:
        """Serialize one compiled executable (atomic write). Returns
        False (with a warning) when this backend cannot serialize or the
        write fails — never raises: persisting is best-effort, the
        in-memory executable is already usable."""
        path = self.path(name, sig)
        try:
            from jax.experimental import serialize_executable as se
            blob, in_tree, out_tree = se.serialize(compiled)
            buf = io.BytesIO()
            devices = compiled.runtime_executable().local_devices()
            pickle.dump({"env": env_fingerprint(), "exe": blob,
                         "in_tree": in_tree, "out_tree": out_tree,
                         "devices": [d.id for d in devices]},
                        buf, protocol=pickle.HIGHEST_PROTOCOL)
            payload = buf.getvalue()
            # unique tmp per writer: replicas of a fleet share one
            # directory, and two processes missing the same entry must
            # not truncate each other's half-written tmp (a fixed
            # '<path>.tmp' name would)
            import tempfile
            fd, tmp = tempfile.mkstemp(dir=self.dir,
                                       prefix=os.path.basename(path),
                                       suffix=".tmp")
            try:
                with os.fdopen(fd, "wb") as f:
                    f.write(_MAGIC + hashlib.md5(payload).digest()
                            + payload)
                    f.flush()
                    os.fsync(f.fileno())
                os.replace(tmp, path)
            except BaseException:
                try:
                    os.remove(tmp)
                except OSError:
                    pass
                raise
        except Exception as e:  # noqa: BLE001 — best-effort persist
            logger.warning(
                "AOT cache save failed for %s (%r); this process keeps "
                "its live-compiled executable, the next cold start pays "
                "the trace again", path, e)
            return False
        self.stats["saved"] += 1
        return True
