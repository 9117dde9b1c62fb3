"""utils/devices.py and chip_smoke.py's gate: host-only, no jit.

The measurement paths must refuse to run without a chip (never fall back to
the CPU backend these tests run on), keep the compile cache where it can be
found again, and take device peaks only from the table.
"""

from __future__ import annotations

import inspect
import os
import re

import jax
import pytest

from torchkafka_tpu.utils import devices

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_require_tpu_raises_on_cpu_naming_the_platform():
    with pytest.raises(RuntimeError, match=r"platform='cpu'"):
        devices.require_tpu()


def test_compile_cache_env_set_leaves_config_alone(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert devices.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_default_is_one_fixed_path_in_the_checkout(
    monkeypatch, tmp_path
):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        paths = []
        for name in ("a", "b"):
            (tmp_path / name).mkdir()
            monkeypatch.chdir(tmp_path / name)
            paths.append(devices.enable_compile_cache())
            assert jax.config.jax_compilation_cache_dir == paths[-1]
    finally:  # the suite itself runs without a persistent cache
        jax.config.update("jax_compilation_cache_dir", before)
    assert paths[0] == paths[1] == os.path.join(REPO, ".jax_cache")
    assert os.path.isabs(paths[0])
    with open(os.path.join(REPO, ".gitignore"), encoding="utf-8") as f:
        assert ".jax_cache/" in f.read().split()


def test_compile_cache_path_has_no_moving_part():
    # The directory is part of the cache key: one derived from a temp
    # dir, a pid or the clock would never hit again.
    src = inspect.getsource(devices.enable_compile_cache) + "".join(
        line for line in inspect.getsource(devices).splitlines(True)
        if "_DEFAULT_CACHE_DIR" in line
    )
    assert not re.search(r"\b(tempfile|getpid|time)\b", src)


def test_device_peaks_unknown_kind_raises():
    assert devices.device_peaks("TPU v5 lite").hbm_bytes_s == 819e9
    with pytest.raises(ValueError, match="no published peaks"):
        devices.device_peaks("TPU v9 imaginary")
    with pytest.raises(ValueError, match="device_kind='cpu'"):
        devices.device_peaks()  # this process's device: the CPU


def test_chip_smoke_without_a_chip_exits_nonzero_before_any_phase(
    monkeypatch, tmp_path, capsys
):
    import chip_smoke

    # Env set: the gate's enable_compile_cache() then touches no config.
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    with pytest.raises(SystemExit) as exc:
        chip_smoke.main([])
    assert exc.value.code not in (0, None)
    assert "platform='cpu'" in str(exc.value.code)
    assert capsys.readouterr().out == ""  # no phase ran, no result line
