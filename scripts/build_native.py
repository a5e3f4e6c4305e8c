#!/usr/bin/env python
"""Rebuild every native component from source (`make native`).

Goes through the runtime's own builder
(gubernator_tpu/native/__init__.py build_component), so the cache names
are exactly the ones the loaders and the TSan suite (tests/test_tsan.py)
look for: `_<flavor>_<component>_<hash>.so`, the hash taken over the
source bytes and the compile command. Other-hash siblings are deleted, so
after editing keydir.cpp or peerlink.cpp one command restores a
verifiable binary set:

    _keydir_<hash>.so          g++ -O2            (runtime)
    _peerlink_<hash>.so        g++ -O2            (runtime)
    _tsan_keydir_<hash>.so     g++ -O1 -g -fsanitize=thread
    _tsan_peerlink_<hash>.so   g++ -O1 -g -fsanitize=thread

`--sanitize` (`make sanitize`) builds the full sanitizer matrix instead:
the TSan pair above plus `_asan_*` (-fsanitize=address) and `_ubsan_*`
(-fsanitize=undefined) variants of both sources.

tests/test_native_build.py is the matching drift check: it fails when a
cached .so was built from other source than the tree's or misses the
exported symbol surface.
"""

import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from gubernator_tpu import native  # noqa: E402

# warnings are errors for the native tier: the sources must stay clean
# under the same -Wall -Wextra sweep guberlint's native-warnings rule
# runs (gubernator_tpu/analysis/rules/native.py) — keep both flag sets
# in lockstep
WARN = ["-Wall", "-Wextra", "-Werror"]


def warn_check(component: str) -> None:
    src, cmd = native.compile_command(component, "")
    subprocess.run([*cmd, *WARN, "-fsyntax-only", src], check=True)


def build(component: str, flavor: str) -> str:
    fresh = not os.path.exists(native.cache_path(component, flavor))
    path = native.build_component(component, flavor)
    print(f"{'built' if fresh else 'cached'}  {os.path.relpath(path, ROOT)}")
    return path


def main(argv=None) -> int:
    argv = sys.argv[1:] if argv is None else argv
    flavors = ("tsan", "asan", "ubsan") if "--sanitize" in argv \
        else ("", "tsan")
    for component in native.COMPONENTS:
        warn_check(component)
        for flavor in flavors:
            build(component, flavor)
    return 0


if __name__ == "__main__":
    sys.exit(main())
