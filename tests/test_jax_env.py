"""The compile-cache helper every JAX entry point calls
(authorino_tpu/utils/jax_env.py): placed from outside when
JAX_COMPILATION_CACHE_DIR is set, at a fixed path under the checkout when
it is not, and keeping every entry whatever its compile time or size."""

import os
import tempfile

import jax

from authorino_tpu.utils import jax_env

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch):
    updates = {}
    monkeypatch.setattr(jax.config, "update",
                        lambda key, value: updates.__setitem__(key, value))
    return updates


def test_sets_no_cache_dir_when_the_environment_places_it(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/dir")
    updates = _recorded_updates(monkeypatch)
    jax_env.setup_jax()
    assert "jax_compilation_cache_dir" not in updates
    # the keep-everything thresholds apply wherever the cache lives
    assert updates["jax_persistent_cache_min_compile_time_secs"] == 0
    assert updates["jax_persistent_cache_min_entry_size_bytes"] == -1


def test_defaults_to_a_fixed_path_under_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    updates = _recorded_updates(monkeypatch)
    jax_env.setup_jax()
    assert updates["jax_compilation_cache_dir"] == \
        os.path.join(ROOT, ".jax_cache") == jax_env.DEFAULT_CACHE_DIR
    # resolved from the package location: not a temp dir, no pid, no time
    assert not jax_env.DEFAULT_CACHE_DIR.startswith(tempfile.gettempdir())
    with open(os.path.join(ROOT, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_appends_the_cpu_backend_behind_a_named_accelerator(monkeypatch):
    """An explicit platform list initialises only what it names; the native
    lane's host twin needs jax.devices("cpu") next to the accelerator."""
    updates = _recorded_updates(monkeypatch)
    monkeypatch.setattr(type(jax.config), "jax_platforms", "tpu",
                        raising=False)
    jax_env.setup_jax()
    assert updates["jax_platforms"] == "tpu,cpu"
    updates.clear()
    monkeypatch.setattr(type(jax.config), "jax_platforms", "tpu,cpu",
                        raising=False)
    jax_env.setup_jax()
    assert "jax_platforms" not in updates


def test_process_info_names_the_device_as_jax_reports_it():
    info = jax_env.jax_process_info()
    d = jax.devices()
    assert info["platform"] == d[0].platform
    assert info["device_kind"] == d[0].device_kind
    assert info["device_count"] == len(d)
    assert info["jax"] == jax.__version__
    assert set(info["compile_cache"]) == {"dir", "hits", "misses"}
