"""Device honesty of the entry points (ISSUE 21): nothing lands on the
CPU, or on a fallback, while reporting success.

* ``chip_smoke.py`` off a TPU — in the repo or alone in a directory —
  exits non-zero and prints no result line;
* the daemon's ``--platform`` default follows the backend JAX finds, and
  a request for a platform JAX did not give fails at boot;
* the compile cache sits where ``JAX_COMPILATION_CACHE_DIR`` says, else
  at the fixed in-checkout path;
* the roofline peak table answers nothing for a device it does not
  know, and the utilization gauge is then absent and counted.
"""
import os
import pathlib
import shutil
import subprocess
import sys

import jax
import pytest

from crdt_tpu.__main__ import check_platform, select_platform
from crdt_tpu.utils import compile_cache

REPO = pathlib.Path(__file__).resolve().parents[1]


@pytest.mark.parametrize("where", ["repo", "alone"])
def test_chip_smoke_refuses_cpu(where, tmp_path):
    script = REPO / "chip_smoke.py"
    if where == "alone":
        script = pathlib.Path(shutil.copy(script, tmp_path))
    p = subprocess.run(
        [sys.executable, str(script)], cwd=script.parent,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
        capture_output=True, text=True, timeout=300)
    assert p.returncode != 0, p.stdout + p.stderr
    assert '"ok": true' not in p.stdout
    assert "no TPU" in p.stderr


def test_daemon_platform_default_follows_backend():
    from crdt_tpu import __main__ as cli

    assert select_platform("auto") == jax.default_backend()
    assert check_platform("auto", "tpu") == "tpu"
    assert check_platform("cpu", "cpu") == "cpu"
    with pytest.raises(SystemExit, match="--platform tpu"):
        check_platform("tpu", "cpu")
    src = pathlib.Path(cli.__file__).read_text()
    assert 'default="auto"' in src


def test_crash_soak_daemons_pin_the_cpu():
    src = (REPO / "crdt_tpu/harness/crashsoak.py").read_text()
    assert '"--platform", "cpu"' in src


def test_compile_cache_dir_env_then_checkout(monkeypatch):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    assert compile_cache.cache_dir() == str(REPO / ".jax_cache")
    monkeypatch.setenv(compile_cache.ENV, "/some/where")
    assert compile_cache.cache_dir() == "/some/where"


@pytest.mark.parametrize("env", [None, "/from/env"])
def test_compile_cache_enable(monkeypatch, env):
    if env is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV, env)
    assert compile_cache.enable() is None  # the CPU keeps it off
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in keys}
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    try:
        jax.config.update("jax_compilation_cache_dir", None)
        path = compile_cache.enable()
        assert path == (env or str(REPO / ".jax_cache"))
        # with the env var set, JAX reads it itself; nothing is set in code
        want = None if env else path
        assert jax.config.jax_compilation_cache_dir == want
        assert jax.config.jax_enable_compilation_cache
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)


def test_peak_table_unknown_device_gets_nothing():
    from crdt_tpu.api.node import ReplicaNode
    from crdt_tpu.obs import devtime
    from crdt_tpu.obs.registry import MetricsRegistry
    from crdt_tpu.utils.metrics import Metrics

    assert devtime.hbm_peak("TPU v5 lite") == 819e9
    assert devtime.hbm_peak("cpu") is None
    assert devtime.hbm_peak("TPU v99") is None
    devtime._dispatch_counts.pop(("0", "merge"), None)
    node = ReplicaNode(rid=0, capacity=64,
                       metrics=Metrics(registry=MetricsRegistry()))
    node.add_command({"a": "1"})
    reg = node.metrics.registry
    assert reg.gauge_value("join_hbm_utilization",
                           node="0", kind="merge") is None
    if reg.gauge_value("join_bytes_per_dispatch", node="0", kind="merge"):
        assert reg.counter_value("join_peak_unknown",
                                 node="0", kind="merge") == 1
