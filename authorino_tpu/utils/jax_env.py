"""Process-level JAX set-up, shared by every entry point that initialises
JAX (cli.run_server, bench.py / bench_micro.py main, runtime/
restart_harness.py): the persistent compilation cache, the CPU backend the
native lane's host twin needs next to the accelerator, and the facts about
the backend that /debug/vars reports.

Compile cache placement: where ``JAX_COMPILATION_CACHE_DIR`` is set JAX
reads it itself and no directory is set in code; otherwise the cache lives
at ``<checkout>/.jax_cache`` — a fixed path resolved from the package
location, because the path is part of the cache key's environment and a
directory that moves never hits.  Entries are kept whatever their compile
time or size: the warm grid is many small executables JAX's default
thresholds would skip."""

from __future__ import annotations

import functools
import os
import threading
from typing import Any, Dict

__all__ = ["DEFAULT_CACHE_DIR", "setup_jax", "jax_process_info"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_cache_events = {"hits": 0, "misses": 0}
_lock = threading.Lock()
_listening = False


def _on_event(event: str, **_: Any) -> None:
    if event == _HIT:
        with _lock:
            _cache_events["hits"] += 1
    elif event == _MISS:
        with _lock:
            _cache_events["misses"] += 1


def setup_jax() -> str:
    """Configure JAX for this process; call before the first compile.
    Returns the persistent compile-cache directory in effect."""
    global _listening
    import jax

    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    # an explicit platform list initialises only what it names, and the
    # native lane's host twin runs on jax.devices("cpu"): keep the named
    # accelerator first (the default, failing loudly if absent) and append
    # the CPU backend behind it
    platforms = jax.config.jax_platforms
    if platforms and "cpu" not in platforms.split(","):
        jax.config.update("jax_platforms", platforms + ",cpu")
    with _lock:
        if not _listening:
            jax.monitoring.register_event_listener(_on_event)
            _listening = True
    return str(jax.config.jax_compilation_cache_dir)


@functools.lru_cache(maxsize=1)
def _backend_facts() -> Dict[str, Any]:
    """Fixed for the life of the process once the backend is up."""
    import jax
    import jaxlib

    from importlib.metadata import PackageNotFoundError, version

    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:  # a CPU/GPU-only installation
        libtpu = None
    devices = jax.devices()
    return {
        "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices),
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "libtpu": libtpu,
    }


def jax_process_info() -> Dict[str, Any]:
    """What the kernels run on, as JAX reports it — the /debug/vars
    ``process`` facts (platform, device kind and count, library versions,
    compile-cache placement and the entries read/written so far)."""
    import jax

    with _lock:
        events = dict(_cache_events)
    return {
        **_backend_facts(),
        "compile_cache": {
            "dir": jax.config.jax_compilation_cache_dir,
            **events,
        },
    }
