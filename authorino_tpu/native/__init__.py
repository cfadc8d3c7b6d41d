"""Native (C++) runtime components.

``native/encoder.cpp`` + ``native/pymod.cpp`` build into one extension
module (``_atpuenc``) implementing the host half of the hot path — selector
walk → gjson-String render → intern lookup → tensor scatter — with two
front-ends:

  - ``encode_docs``: walks the Python dict documents directly (no JSON
    round-trip); default.
  - ``encode_json``: parses a JSON blob GIL-free with threads — wins on
    many-core hosts / large batches (AUTHORINO_TPU_ENCODE_MODE=json).

compiler/encode.py's Python implementation is the semantic reference and the
automatic fallback.  Builds on first use with the baked-in g++ (no pip
deps); AUTHORINO_TPU_NATIVE=0 forces the Python path.

Staleness is keyed on a digest of the C++ sources stored beside the ``.so``
(not on mtimes: a copied tree can carry a stale ``.so`` that is newer than
the sources it was not built from), and ``source_digest()`` names what the
loaded library was built from.
"""

from __future__ import annotations

import hashlib
import importlib.util
import logging
import os
import subprocess
import sysconfig
import threading

__all__ = ["load_library", "native_enabled", "source_digest", "loaded_digest",
           "NativeEncoder", "get_native_encoder"]

log = logging.getLogger("authorino_tpu.native")

_NATIVE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))), "native")
_BUILD_DIR = os.path.join(os.path.dirname(os.path.abspath(__file__)), "_build")
_LIB_PATH = os.path.join(_BUILD_DIR, "_atpuenc.so")
_DIGEST_PATH = _LIB_PATH + ".src-sha256"
# what ``source_digest()`` covers: the benchmark's harness computes the same
# digest over the same three names (benchmark/child.py NATIVE_SOURCES) and
# refuses a server that reports another
_SOURCES = ("encoder.cpp", "frontend.cpp", "pymod.cpp")
# every source of the extension: the build's staleness key
_BUILD_SOURCES = _SOURCES + ("verdict_cache.cpp",)

_lock = threading.Lock()
_mod = None
_mod_digest = None
_load_failed = False


def native_enabled() -> bool:
    return os.environ.get("AUTHORINO_TPU_NATIVE", "1") not in ("0", "false", "no")


def source_digest(names=_SOURCES) -> str:
    """sha256 over the extension's C++ sources as they stand on disk."""
    h = hashlib.sha256()
    for name in names:
        with open(os.path.join(_NATIVE_DIR, name), "rb") as f:
            h.update(name.encode() + b"\0" + f.read() + b"\0")
    return h.hexdigest()


def loaded_digest():
    """``source_digest()`` of what the LOADED extension was built from
    (None before a successful ``load_library``)."""
    return _mod_digest


def _built_digest() -> str:
    """Digest recorded beside the ``.so`` at build time ("" when absent)."""
    try:
        with open(_DIGEST_PATH) as f:
            return f.read().strip()
    except OSError:
        return ""


def _build(digest: str) -> bool:
    os.makedirs(_BUILD_DIR, exist_ok=True)
    cmd = [
        "g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-pthread",
        "-I", sysconfig.get_paths()["include"],
        os.path.join(_NATIVE_DIR, "pymod.cpp"),
        "-o", _LIB_PATH + ".tmp",
    ]
    try:
        subprocess.run(cmd, check=True, capture_output=True, timeout=180)
        # digest away first: a crash between the two renames leaves a .so
        # with no digest (rebuilt next time), never one with a wrong digest
        if os.path.exists(_DIGEST_PATH):
            os.remove(_DIGEST_PATH)
        os.replace(_LIB_PATH + ".tmp", _LIB_PATH)
        with open(_DIGEST_PATH + ".tmp", "w") as f:
            f.write(digest + "\n")
        os.replace(_DIGEST_PATH + ".tmp", _DIGEST_PATH)
        return True
    except (subprocess.SubprocessError, OSError) as e:
        detail = getattr(e, "stderr", b"")
        log.warning("native encoder build failed (%s); using Python encoder: %s",
                    e, detail.decode()[:500] if detail else "")
        return False


def load_library():
    """Build (if stale) and import the _atpuenc extension; None on failure."""
    global _mod, _mod_digest, _load_failed
    if _mod is not None or _load_failed or not native_enabled():
        return _mod
    with _lock:
        if _mod is not None or _load_failed:
            return _mod
        try:
            digest = source_digest()
            build_key = source_digest(_BUILD_SOURCES)
        except OSError as e:
            log.warning("native sources unreadable: %s", e)
            _load_failed = True
            return None
        stale = not os.path.exists(_LIB_PATH) or _built_digest() != build_key
        if stale and not _build(build_key):
            _load_failed = True
            return None
        try:
            spec = importlib.util.spec_from_file_location("_atpuenc", _LIB_PATH)
            mod = importlib.util.module_from_spec(spec)
            spec.loader.exec_module(mod)
        except Exception as e:
            log.warning("native encoder load failed: %s", e)
            _load_failed = True
            return None
        _mod, _mod_digest = mod, digest
        return _mod


from .encoder import NativeEncoder, get_native_encoder  # noqa: E402,F401
