"""Boot-time device settings (utils/platform.py) and the places that must
not hide the device: donation is required, the compile cache is placed
from outside, a failed native build is loud, and the multi-chip dry run
needs its devices in its own process."""

import logging
import os
import sys

import pytest

import jax

from gubernator_tpu import native
from gubernator_tpu.utils import platform

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_donation_is_taken_or_boot_fails(monkeypatch):
    platform.donation_supported.cache_clear()
    assert platform.donation_supported() is True
    # a backend that leaves the donated buffer alive is a failure, not
    # a mode
    platform.donation_supported.cache_clear()
    monkeypatch.setattr(jax, "jit", lambda f, **kw: f)
    with pytest.raises(RuntimeError, match="donated"):
        platform.donation_supported()
    platform.donation_supported.cache_clear()


class TestCompileCacheDir:
    def test_environment_wins_and_nothing_is_set(self, monkeypatch):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/some/where")
        assert platform.compile_cache_dir() == "/some/where"
        assert jax.config.jax_compilation_cache_dir == before

    def test_fixed_directory_inside_the_checkout(self, monkeypatch):
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            assert platform.compile_cache_dir() == \
                os.path.join(ROOT, ".jax_cache")
            assert jax.config.jax_compilation_cache_dir == \
                os.path.join(ROOT, ".jax_cache")
            # same answer every time: no pid, no time, no temp name
            assert platform.compile_cache_dir() == platform.compile_cache_dir()
        finally:
            jax.config.update("jax_compilation_cache_dir", before)


def test_device_facts_on_the_engine():
    from gubernator_tpu.models.engine import Engine

    dev = Engine(capacity=256).device
    assert dev["platform"] == "cpu" and dev["device_count"] == 1
    assert dev["table_bytes_per_device"] == [256 * 64]
    assert dev["table_layout"] == "u32[C,16]"
    assert dev["donation"] is True
    assert dev["key_directory"] == "native"
    assert dev["visible_device_count"] == jax.local_device_count()


def test_device_facts_on_the_mesh():
    from gubernator_tpu.parallel import ShardedEngine

    dev = ShardedEngine(n_shards=4, capacity_per_shard=128).device
    assert dev["device_count"] == 4 and len(set(dev["devices"])) == 4
    assert dev["table_bytes_per_device"] == [128 * 64] * 4
    assert dev["table_layout"] == "u32[C,16]"


class TestNativeFallbackIsLoud:
    def test_failed_build_logs_the_compiler(self, monkeypatch, caplog):
        def refuse(capacity):
            raise native.NativeBuildError("g++ exited 1:\nkeydir.cpp: boom")

        monkeypatch.setattr(native, "NativeKeyDirectory", refuse)
        with caplog.at_level(logging.WARNING, logger="gubernator_tpu.native"):
            d = native.make_key_directory(64)
        assert type(d).__name__ == "KeyDirectory"
        assert "keydir.cpp: boom" in caplog.text

    def test_guber_no_native_is_the_quiet_way(self, monkeypatch, caplog):
        monkeypatch.setenv("GUBER_NO_NATIVE", "1")
        with caplog.at_level(logging.WARNING, logger="gubernator_tpu.native"):
            d = native.make_key_directory(64)
        assert type(d).__name__ == "KeyDirectory"
        assert caplog.text == ""

    def test_compiler_verdict_is_an_error_with_stderr(self, monkeypatch):
        monkeypatch.setitem(native.COMPONENTS, "broken",
                            ("tsan.supp", []))  # not C++
        with pytest.raises(native.NativeBuildError, match="exited"):
            native.build_component("broken")
        leftovers = [n for n in os.listdir(os.path.dirname(native.__file__))
                     if n.startswith("_broken_")]
        assert leftovers == []


class TestDryrunMultichip:
    def test_every_answer_equals_the_oracle(self):
        sys.path.insert(0, ROOT)
        import __graft_entry__ as g

        out = g.dryrun_multichip(8)
        assert out["mesh"] == [2, 4] and len(out["devices"]) == 8
        assert out["global_syncs"] == 2
        assert out["answers_equal_to_oracle"] == 100

    def test_too_few_devices_raises_with_the_command(self):
        sys.path.insert(0, ROOT)
        import __graft_entry__ as g

        with pytest.raises(RuntimeError, match="xla_force_host_platform"):
            g.dryrun_multichip(jax.device_count() + 1)
