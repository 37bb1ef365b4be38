"""Persistent XLA compilation cache wiring (session._init_compilation_cache)."""

import os

import jax
import numpy as np
import pytest

from sparkdq4ml_tpu import TpuSession


@pytest.fixture(autouse=True)
def _restore_jax_cache_config():
    """These tests mutate process-global jax config; restore it so the rest
    of the suite compiles with its original cache behavior."""
    saved = {k: getattr(jax.config, k) for k in (
        "jax_compilation_cache_dir",
        "jax_enable_compilation_cache",
        "jax_persistent_cache_min_compile_time_secs",
        "jax_persistent_cache_min_entry_size_bytes")}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)
    from jax.experimental.compilation_cache import compilation_cache as cc

    cc.reset_cache()


def _cache_entries(path):
    return sorted(n for n in os.listdir(path) if n.endswith("-cache"))


def test_unset_env_uses_the_fixed_in_checkout_dir(monkeypatch):
    from sparkdq4ml_tpu import session as sess_mod

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert sess_mod.COMPILE_CACHE_DIR == os.path.join(repo, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", None)
    s = TpuSession.builder().app_name("t").get_or_create()
    try:
        assert jax.config.jax_compilation_cache_dir \
            == sess_mod.COMPILE_CACHE_DIR
        assert os.path.isdir(sess_mod.COMPILE_CACHE_DIR)
    finally:
        s.stop()


def test_env_dir_is_left_alone_and_is_the_one_that_fills(tmp_path,
                                                         monkeypatch):
    from sparkdq4ml_tpu import session as sess_mod

    placed = tmp_path / "placed-from-outside"
    placed.mkdir()
    # a cache the driver put there: unstamped entries and a foreign file
    (placed / "jit_foreign-entry-cache").write_bytes(b"\x00foreign")
    (placed / "notes.txt").write_text("not a cache entry")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(placed))
    # what jax itself does with the variable at import time
    jax.config.update("jax_compilation_cache_dir", str(placed))
    updates = []
    real_update = jax.config.update
    monkeypatch.setattr(
        jax.config, "update",
        lambda k, v: (updates.append(k), real_update(k, v))[1])
    default_before = (_cache_entries(sess_mod.COMPILE_CACHE_DIR)
                      if os.path.isdir(sess_mod.COMPILE_CACHE_DIR) else [])
    placed_before = _cache_entries(placed)
    s = TpuSession.builder().app_name("t").get_or_create()
    try:
        assert "jax_compilation_cache_dir" not in updates
        assert jax.config.jax_compilation_cache_dir == str(placed)
        jax.jit(lambda x: x * 3.0 + 41.5)(np.arange(8.0)).block_until_ready()
        assert (placed / "jit_foreign-entry-cache").read_bytes() \
            == b"\x00foreign"
        assert (placed / "notes.txt").exists()
        assert not (placed / "host_key.json").exists()
        assert len(_cache_entries(placed)) >= 2      # gained an entry
        added = set(_cache_entries(placed)) - set(placed_before)
        default_after = (_cache_entries(sess_mod.COMPILE_CACHE_DIR)
                         if os.path.isdir(sess_mod.COMPILE_CACHE_DIR)
                         else [])
        # other test processes may fill the default directory meanwhile;
        # what this compile wrote must not be among what it gained
        assert not added & (set(default_after) - set(default_before))
    finally:
        s.stop()


def test_cache_opt_out_and_back_on():
    before = jax.config.jax_compilation_cache_dir
    s = (TpuSession.builder().app_name("t")
         .config("spark.compilation.cache", "off").get_or_create())
    try:
        assert jax.config.jax_enable_compilation_cache is False
        # the directory is never cleared — opting out flips the switch
        assert jax.config.jax_compilation_cache_dir == before
        s2 = (TpuSession.builder()
              .config("spark.compilation.cache", "on").get_or_create())
        assert s2 is s
        assert jax.config.jax_enable_compilation_cache is True
    finally:
        s.stop()
        jax.config.update("jax_enable_compilation_cache", True)


def test_master_tpu_raises_on_another_backend():
    # no probe, no fallback: the accelerator was demanded, the default
    # backend is the CPU, so the session refuses instead of degrading
    with pytest.raises(RuntimeError, match="default backend here is 'cpu'"):
        TpuSession.builder().master("tpu[*]").get_or_create()
    assert TpuSession.active() is None


class TestDistributedInit:
    """Multi-host bootstrap wiring (session._init_distributed). The real
    jax.distributed.initialize needs a pod; assert the dispatch logic."""

    def test_local_master_does_not_initialize(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: calls.append(kw))
        s = TpuSession.builder().master("local[*]").get_or_create()
        try:
            assert calls == []
        finally:
            s.stop()

    def test_pod_master_initializes(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: calls.append(kw))
        # force the "not yet initialized" branch
        from jax._src import distributed as dist
        monkeypatch.setattr(dist.global_state, "client", None,
                            raising=False)
        s = TpuSession.builder().master("pod").get_or_create()
        try:
            assert calls == [{}]  # pod auto-bootstrap: env-derived
        finally:
            s.stop()

    def test_explicit_coordinator_conf(self, monkeypatch):
        calls = []
        monkeypatch.setattr(jax.distributed, "initialize",
                            lambda **kw: calls.append(kw))
        from jax._src import distributed as dist
        monkeypatch.setattr(dist.global_state, "client", None,
                            raising=False)
        s = (TpuSession.builder().master("local[*]")
             .config("spark.distributed.coordinator", "10.0.0.1:8476")
             .config("spark.distributed.numProcesses", 4)
             .config("spark.distributed.processId", 2).get_or_create())
        try:
            assert calls == [{"coordinator_address": "10.0.0.1:8476",
                              "num_processes": 4, "process_id": 2}]
        finally:
            s.stop()
