"""What the chip bring-up added around the entry points: the placeable
compile cache and re-exec decisions that never touch JAX."""

import importlib.util
import os
import pathlib
import subprocess
import sys

import jax
import pytest

from solvingpapers_tpu import compile_cache, hostenv

REPO = pathlib.Path(__file__).resolve().parent.parent


def _load(name):
    spec = importlib.util.spec_from_file_location(name, REPO / f"{name}.py")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


# ------------------------------------------------------------ compile cache


def test_cache_dir_from_the_environment_is_left_to_jax(
        monkeypatch, cache_dir_restored):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before


def test_cache_dir_is_one_fixed_path_in_the_checkout(
        monkeypatch, cache_dir_restored):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.configure_compile_cache()
    second = compile_cache.configure_compile_cache()
    assert first == second == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first


def test_cache_dir_is_the_same_in_another_process(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    code = (
        "import jax\n"
        "from solvingpapers_tpu.compile_cache import configure_compile_cache\n"
        "print(configure_compile_cache())\n"
        "print(jax.config.jax_compilation_cache_dir)\n"
    )
    outs = [
        subprocess.run(
            [sys.executable, "-c", code], cwd=REPO, text=True, check=True,
            capture_output=True, env={**os.environ, "JAX_PLATFORMS": "cpu"},
        ).stdout.split()
        for _ in range(2)
    ]
    assert outs[0] == outs[1] == [str(REPO / ".jax_cache")] * 2


def test_the_suite_keeps_the_persistent_cache_off():
    assert jax.config.jax_enable_compilation_cache is False


# ------------------------------------------------------------------ hostenv


@pytest.mark.parametrize("env,expected", [
    ({}, 0),
    ({"JAX_PLATFORMS": "tpu"}, 0),
    ({"JAX_PLATFORMS": "cpu"}, 1),
    ({"JAX_PLATFORMS": "cpu",
      "XLA_FLAGS": "--foo --xla_force_host_platform_device_count=8"}, 8),
    ({"XLA_FLAGS": "--xla_force_host_platform_device_count=8"}, 0),
])
def test_virtual_cpu_devices_reads_only_the_environment(env, expected):
    assert hostenv.virtual_cpu_devices(env) == expected


def test_virtual_cpu_env_replaces_the_count_and_pins_the_cpu():
    env = hostenv.virtual_cpu_env(4, {
        "XLA_FLAGS": "--a=1 --xla_force_host_platform_device_count=8",
        "JAX_PLATFORMS": "tpu", "KEEP": "1",
    })
    assert env["JAX_PLATFORMS"] == "cpu" and env["KEEP"] == "1"
    assert env["XLA_FLAGS"].count("device_count") == 1
    assert hostenv.virtual_cpu_devices(env) == 4


def test_dryrun_reexec_is_decided_without_touching_jax(monkeypatch):
    """With no virtual devices in the environment the dryrun must spawn
    its CPU child WITHOUT the parent asking JAX for devices first (a
    parent that did would hold the chip while the child runs)."""
    graft = _load("__graft_entry__")
    monkeypatch.setenv("JAX_PLATFORMS", "tpu")
    monkeypatch.setattr(
        jax, "devices",
        lambda *a, **k: pytest.fail("parent touched jax.devices()"),
    )
    calls = []

    def fake_run(cmd, env, **kw):
        calls.append(env)
        return subprocess.CompletedProcess(cmd, 0)

    monkeypatch.setattr(subprocess, "run", fake_run)
    graft.dryrun_multichip(4)
    assert len(calls) == 1 and hostenv.virtual_cpu_devices(calls[0]) == 4
