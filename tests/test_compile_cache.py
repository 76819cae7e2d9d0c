"""Where the persistent compile cache goes (sat_tpu/utils/compile_cache.py):
an outer JAX_COMPILATION_CACHE_DIR is left alone — the code sets no
directory — and otherwise it is one fixed path inside the checkout, the
same on every machine."""

import os
import types

from sat_tpu.utils import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fake_jax():
    updates = {}
    config = types.SimpleNamespace(update=updates.__setitem__)
    return types.SimpleNamespace(config=config), updates


def test_outer_setting_is_passed_through(monkeypatch, tmp_path):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    jax, updates = _fake_jax()
    assert compile_cache.enable(jax) == str(tmp_path)
    assert "jax_compilation_cache_dir" not in updates
    # thresholds may still be set
    assert updates["jax_persistent_cache_min_entry_size_bytes"] == 0


def test_unset_means_the_fixed_path_in_the_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    jax, updates = _fake_jax()
    assert compile_cache.enable(jax) == os.path.join(REPO, ".jax_cache")
    assert updates["jax_compilation_cache_dir"] == os.path.join(REPO, ".jax_cache")


def test_the_path_does_not_depend_on_the_machine(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    here = compile_cache.cache_dir()
    # what used to key the directory: the host's CPU feature flags
    monkeypatch.setattr("platform.machine", lambda: "some-other-arch")
    monkeypatch.setattr("builtins.open", _no_cpuinfo(open))
    assert compile_cache.cache_dir() == here == compile_cache.DEFAULT_DIR
    assert os.path.dirname(here) == REPO


def _no_cpuinfo(real_open):
    def fake(path, *args, **kwargs):
        if str(path) == "/proc/cpuinfo":
            raise AssertionError("the cache path must not read /proc/cpuinfo")
        return real_open(path, *args, **kwargs)

    return fake
