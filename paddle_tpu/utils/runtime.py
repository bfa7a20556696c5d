"""What a process runs on, and where its compiled programs are kept.

Every entry point that compiles (``trainer/cli.py:main``,
``chip_smoke.py``, ``tools/tpu_evidence.py``) calls ``start`` once,
before the first jit: it places JAX's persistent compilation cache and
logs platform / device kind / count, so no run's log leaves in doubt
whether it was a chip run. Nothing here runs at ``import paddle_tpu``.

JAX falls back to the CPU when it cannot open an accelerator and
``JAX_PLATFORMS`` is unset — which is what happens to the second process
on a one-chip host, since a chip belongs to one process at a time.
``start`` turns that silent fallback into a failure (a CPU run is asked
for with ``JAX_PLATFORMS=cpu``); ``require_tpu`` fails on anything but a
TPU, for the entry points whose numbers mean nothing off the chip.
"""

from __future__ import annotations

import importlib.util
import os
import re
from typing import Dict, Tuple

from paddle_tpu.utils.log import logger

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir() -> Tuple[str, bool]:
    """``(directory, placed_from_outside)``: where ``JAX_COMPILATION_
    CACHE_DIR`` says when it is set (JAX reads the variable itself, so
    code sets nothing), otherwise ``<checkout>/.jax_cache``: one fixed
    place, so every process of a checkout finds what the others
    compiled. A directory that moves never hits, so it is never a temp
    name, a pid or a timestamp."""
    env = os.environ.get(CACHE_ENV)
    if env:
        return env, True
    return os.path.join(_CHECKOUT, ".jax_cache"), False


def enable_compile_cache() -> str:
    """Place the persistent compilation cache (before the first compile:
    JAX decides once per process whether a cache is in use). Returns the
    directory in use."""
    import jax
    path, from_env = compile_cache_dir()
    if not from_env:
        jax.config.update("jax_compilation_cache_dir", path)
    # JAX's key leaves the names out by default, so two programs that
    # differ only in their named scopes share an entry, and the second
    # is handed the first's ``op_name`` paths: the device trace is read
    # by those (docs/observability.md), so they belong to the key. The
    # key then holds the call sites' files too: written relative to the
    # checkout, or two checkouts of one code would share nothing
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    jax.config.update("jax_hlo_source_file_canonicalization_regex",
                      re.escape(_CHECKOUT + os.sep))
    return path


class CacheCounter:
    """Counts this process's persistent-cache traffic from JAX's own
    monitoring events: ``requests`` (compiles that consulted the cache)
    and ``hits`` (executables it served). A second run of the same
    program in one checkout shows ``hits`` > 0."""

    def __init__(self):
        self.requests = 0
        self.hits = 0

    def _on_event(self, event: str, **_kw) -> None:
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.requests += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def __enter__(self) -> "CacheCounter":
        import jax.monitoring
        jax.monitoring.register_event_listener(self._on_event)
        return self

    def __exit__(self, *exc) -> None:
        import jax.monitoring
        jax.monitoring.unregister_event_listener(self._on_event)

    def snapshot(self) -> Dict[str, int]:
        return {"requests": self.requests, "hits": self.hits}


def device_report() -> Dict[str, object]:
    """The device as JAX reports it — the triple every result row and
    log names: platform, device kind, device count."""
    import jax
    devs = jax.devices()
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def start(what: str) -> Dict[str, object]:
    """Entry-point preamble: place the compile cache and log what the
    process runs on. Fails when this is a TPU installation (libtpu is
    present), no platform was asked for, and JAX still came up without
    the TPU: the chip could not be opened (another process holds it)
    and JAX fell back — such a process must not train or serve from
    the CPU as if nothing had happened."""
    cache = enable_compile_cache()
    dev = device_report()
    logger.info("%s: platform=%s device_kind=%s devices=%d "
                "compile_cache=%s", what, dev["platform"], dev["kind"],
                dev["count"], cache)
    if (dev["platform"] != "tpu" and not os.environ.get("JAX_PLATFORMS")
            and importlib.util.find_spec("libtpu") is not None):
        raise SystemExit(
            f"{what}: libtpu is installed and JAX_PLATFORMS is unset, "
            f"but JAX came up on {dev['platform']!r} — the TPU could "
            "not be opened (a chip belongs to one process at a time) "
            "and JAX fell back. Set JAX_PLATFORMS=cpu to run on the "
            "CPU on purpose.")
    return dev


def require_tpu(what: str) -> Dict[str, object]:
    """``start`` for the entry points whose output means nothing off the
    chip (``chip_smoke.py``, ``tools/tpu_evidence.py``):
    ``SystemExit`` — before anything is placed or printed — unless the
    default backend is a TPU."""
    import jax
    if jax.default_backend() != "tpu":
        dev = device_report()
        raise SystemExit(
            f"{what}: needs a TPU, but JAX's default backend is "
            f"{jax.default_backend()!r} ({dev['count']} x {dev['kind']}; "
            f"JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r}). Run it "
            "on the chip; there is no CPU fallback.")
    return start(what)
