"""Native build hygiene (`make native` + the drift check).

The runtime loads hash-keyed .so caches built from keydir.cpp and
peerlink.cpp (gubernator_tpu/native/__init__.py build_component); the
TSan suite builds its own variants the same way. Those binaries are only
trustworthy if (a) the sources still compile with the exact production
flags, (b) every cached .so in the tree carries the hash of its source's
CURRENT bytes and compile command (another name means the binary was
built from other source), (c) concurrent first users end up with one
whole binary, and (d) the loaded libraries export the full symbol surface
the ctypes registrations bind — including the wire-contract-v2 additions.
"""

import ctypes
import os
import subprocess

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
NATIVE = os.path.join(HERE, "..", "gubernator_tpu", "native")

# source file -> component name of gubernator_tpu.native.COMPONENTS
SOURCES = {
    "keydir.cpp": "keydir",
    "peerlink.cpp": "peerlink",
}

# the ctypes surface each component must export (drift here = a .so
# built from older source than the Python bindings expect)
KEYDIR_SYMBOLS = [
    "keydir_new", "keydir_free", "keydir_lookup_batch", "keydir_mirror_seed",
    "keydir_decide_one", "keydir_mirror_flush", "keydir_drop", "keydir_peek",
    "keydir_dump", "keydir_size", "keydir_evictions", "fnv1a_owner_batch",
    "fnv1a_fingerprint_batch", "keydir_prep_pack_columnar",
    "keydir_prep_route_columnar",
]
PEERLINK_SYMBOLS = [
    "pls_start", "pls_start2", "pls_stop", "pls_port", "pls_next_batch",
    "pls_send_responses", "pls_send_partial", "pls_pending_count",
    "pls_partial_posts", "pls_v2_conns", "pls_set_native",
]


def _compile_check(src_name: str, extra=()):
    """The tier-1-fast rebuild proof: the committed source compiles with
    the production flag set (syntax+type check only — full codegen is
    `make native` / the hash-keyed cache)."""
    src = os.path.join(NATIVE, src_name)
    r = subprocess.run(
        ["g++", "-O2", "-shared", "-fPIC", "-std=c++17", "-fsyntax-only",
         *extra, src],
        capture_output=True, text=True)
    assert r.returncode == 0, f"{src_name} no longer compiles:\n{r.stderr}"


class TestSourcesCompile:
    def test_keydir_compiles(self):
        import sysconfig

        _compile_check("keydir.cpp",
                       [f"-I{sysconfig.get_paths()['include']}"])

    def test_peerlink_compiles(self):
        _compile_check("peerlink.cpp")


class TestCacheDrift:
    @pytest.mark.parametrize("component", sorted(SOURCES.values()))
    def test_cached_so_matches_source_hash(self, component):
        """Every cached .so present for a component must carry the hash
        of the source's CURRENT bytes and flags in its name — a mismatch
        means the binary was built from different source than what's in
        the tree (the unverifiable-binary failure `make native` fixes).
        Independent of mtimes: a copied tree keeps its caches valid."""
        from gubernator_tpu import native

        native.build_component(component)  # prunes other-hash siblings
        for flavor in ("", "tsan"):
            prefix = native.cache_prefix(component, flavor)
            want = os.path.basename(native.cache_path(component, flavor))
            for name in os.listdir(NATIVE):
                if name.startswith(prefix) and name.endswith(".so"):
                    assert name == want, (
                        f"{name} drifted from {component}.cpp "
                        f"(expected {want}): run `make native`")

    def test_key_covers_source_bytes_and_flags(self, monkeypatch):
        """The cache key changes with the flags and with the source
        bytes, and with nothing else (mtime least of all)."""
        from gubernator_tpu import native

        src = os.path.join(NATIVE, "keydir.cpp")
        key = native.source_key("keydir")
        st = os.stat(src)
        os.utime(src, (st.st_atime, st.st_mtime + 1000))
        try:
            assert native.source_key("keydir") == key
        finally:
            os.utime(src, (st.st_atime, st.st_mtime))
        assert native.source_key("keydir", "tsan") != key
        monkeypatch.setitem(native.FLAVORS, "", ["-O1"])
        assert native.source_key("keydir") != key

    def test_loader_builds_current_cache(self):
        """load_library()/load_peerlink() must land on (or build) the
        current-hash cache, never another one."""
        from gubernator_tpu import native

        native.load_library()
        native.load_peerlink()
        for component in SOURCES.values():
            assert os.path.exists(native.cache_path(component))

    def test_six_concurrent_first_loads(self, tmp_path):
        """Six processes load both libraries at once against an empty
        cache (the driver's six xdist workers): six successes, and
        exactly one .so per component afterwards. The cache lives in a
        scratch copy of the native directory so the race never empties
        the one the other tests are loading from."""
        import shutil
        import sys

        pkg = tmp_path / "gubernator_tpu"
        root = os.path.join(NATIVE, "..")
        shutil.copytree(
            root, pkg,
            ignore=shutil.ignore_patterns("*.so", "__pycache__", "*.lock"))
        code = (
            "from gubernator_tpu import native\n"
            "native.load_library(); native.load_peerlink(); "
            "native.load_pydll()\n"
            "assert native.NativeKeyDirectory(64).lookup(['a'])[0] == [0]\n"
            "print(native.cache_path('keydir'))\n")
        env = dict(os.environ, PYTHONPATH=str(tmp_path), JAX_PLATFORMS="cpu")
        procs = [
            subprocess.Popen([sys.executable, "-c", code], cwd=tmp_path,
                             env=env, stdout=subprocess.PIPE,
                             stderr=subprocess.PIPE, text=True)
            for _ in range(6)]
        outs = [p.communicate(timeout=240) for p in procs]
        for p, (out, err) in zip(procs, outs):
            assert p.returncode == 0, err[-2000:]
            assert out.strip().startswith(str(pkg)), out
        built = sorted(n for n in os.listdir(pkg / "native")
                       if n.endswith(".so") or n.endswith(".tmp"))
        assert len(built) == 2, built
        assert built[0].startswith("_keydir_"), built
        assert built[1].startswith("_peerlink_"), built


class TestSymbolSurface:
    def test_keydir_exports(self):
        from gubernator_tpu import native

        lib = native.load_library()
        for sym in KEYDIR_SYMBOLS:
            assert hasattr(lib, sym), f"keydir.cpp lost export {sym}"

    def test_peerlink_exports(self):
        from gubernator_tpu import native

        lib = native.load_peerlink()
        for sym in PEERLINK_SYMBOLS:
            assert hasattr(lib, sym), f"peerlink.cpp lost export {sym}"

    @pytest.mark.slow
    def test_tsan_variants_build_and_export(self):
        """The `make native` tsan flavors build from the same source and
        carry the same surface (tests/test_tsan.py loads them by name)."""
        from gubernator_tpu import native

        for component, symbols in (("peerlink", PEERLINK_SYMBOLS),
                                   ("keydir", KEYDIR_SYMBOLS)):
            path = native.build_component(component, "tsan")
            nm = subprocess.run(["nm", "-D", path], capture_output=True,
                                text=True, check=True).stdout
            for sym in symbols:
                assert f" T {sym}" in nm, f"{path} lost export {sym}"
