"""Process-level JAX set-up, shared by every entry point that initialises
JAX (cli.run_server, runtime/restart_harness.py): the persistent compilation cache, the CPU backend the
native lane's host twin needs next to the accelerator, and the facts about
the backend that /debug/vars reports: what it is, how long the process took
to bring it up and to be ready behind it, and what each device holds.

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
import time
from typing import Any, Dict

__all__ = ["DEFAULT_CACHE_DIR", "setup_jax", "jax_process_info", "boot_stamp"]

DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))),
    ".jax_cache")

_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"
_cache_events = {"hits": 0, "misses": 0}
_lock = threading.Lock()
_listening = False
_IMPORTED = time.monotonic()
# seconds since the process started, taken where each phase of the boot ends
# (cli.run_server): backend_s, reconcile_s, warm_s
_boot: Dict[str, float] = {}


def _since_process_start() -> float:
    """Seconds since the kernel started this process (/proc/self/stat field
    22 against CLOCK_BOOTTIME), so interpreter start and imports count;
    where /proc says nothing, since this module was imported."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        return (time.clock_gettime(time.CLOCK_BOOTTIME)
                - start_ticks / os.sysconf("SC_CLK_TCK"))
    except (OSError, ValueError, IndexError, AttributeError):
        return time.monotonic() - _IMPORTED


def boot_stamp(name: str) -> None:
    """Mark the end of one boot phase, once: a later reconcile or warm grid
    is not the boot's."""
    with _lock:
        _boot.setdefault(name, round(_since_process_start(), 3))


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


def _device_memory() -> list:
    """memory_stats() of each local device, the three numbers a reader
    needs; [] where the backend reports none (the CPU)."""
    import jax

    out = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if stats:
            out.append({"id": d.id, **{k: int(stats[k]) for k in (
                "bytes_in_use", "peak_bytes_in_use", "bytes_limit")
                if k in stats}})
    return out


def jax_process_info() -> Dict[str, Any]:
    """What the kernels run on, as JAX reports it — the /debug/vars
    ``process`` facts (platform, device kind and count, library versions,
    compile-cache placement and the entries read/written so far, the boot
    stamps, each device's memory)."""
    import jax

    with _lock:
        events = dict(_cache_events)
        boot = dict(_boot)
    return {
        **_backend_facts(),
        "compile_cache": {
            "dir": jax.config.jax_compilation_cache_dir,
            **events,
        },
        "boot": boot,
        "device_memory": _device_memory(),
    }
