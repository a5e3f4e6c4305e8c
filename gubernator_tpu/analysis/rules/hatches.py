"""`escape-hatch` — every perf-path feature flag has a differential test.

The standing constraint (ROADMAP) that let the pipeline, lease, wire-v2,
and reshard refactors land safely: a perf path ships with a lock-step /
serial / off escape hatch, and a test proves the hatch bit-identical to
the old behavior. This rule pins the second half mechanically: for each
registered hatch, at least one file under `tests/` must reference the
flag (env name or its BehaviorConfig/DaemonConfig attribute) AND carry a
differential marker ("differential", "bit-identical", "lock-step",
"byte-identical") — the vocabulary every such test in this repo already
uses. A hatch whose differential test is deleted or renamed away fails
tier-1 at that PR, not at the next 3 a.m. bisect.
"""

from __future__ import annotations

import re
from typing import Iterable, List, Sequence, Tuple

from gubernator_tpu.analysis.core import Finding, RepoIndex, Rule, register

# (env knob, source-level aliases a test may use instead of the env name)
HATCHES: Sequence[Tuple[str, Tuple[str, ...]]] = (
    ("GUBER_HOT_LEASES", ("hot_leases",)),
    ("GUBER_RESHARD", ("reshard",)),
    ("GUBER_PIPELINE_DEPTH", ("pipeline_depth",)),
    ("GUBER_DEVICE_DIRECTORY", ("device_directory", "DevDirEngine")),
    ("GUBER_PROFILE", ("profile_enabled",)),
    ("GUBER_LOCK_WITNESS", ("lock_witness", "witness_enabled")),
    ("GUBER_LEDGER", ("ledger_enabled",)),
    ("GUBER_AUTOPILOT", ("autopilot",)),
)

DIFF_RE = re.compile(
    r"differential|bit.?identical|lock.?step|byte.?identical",
    re.IGNORECASE)

TESTS_DIR = "tests"
ENVCONF = "gubernator_tpu/cmd/envconf.py"


@register
class EscapeHatchRule(Rule):
    id = "escape-hatch"
    doc = ("every perf-path feature flag must be exercised by a tests/ "
           "file containing a differential assertion marker")

    # overridable for the corpus harness
    hatches: Sequence[Tuple[str, Tuple[str, ...]]] = HATCHES

    def check(self, repo: RepoIndex) -> Iterable[Finding]:
        test_files = repo.walk(TESTS_DIR, ".py")
        for env, aliases in self.hatches:
            tokens = (env,) + aliases
            referencing: List[str] = []
            differential = False
            for relpath in test_files:
                text = repo.get(relpath).text
                if any(t in text for t in tokens):
                    referencing.append(relpath)
                    if DIFF_RE.search(text):
                        differential = True
            if differential:
                continue
            path, line = self._anchor(repo, env)
            if not referencing:
                yield Finding(
                    self.id, path, line,
                    f"escape hatch {env} has no test under tests/ "
                    "referencing it — a hatch nobody exercises is a "
                    "hatch that silently rotted shut")
            else:
                yield Finding(
                    self.id, path, line,
                    f"escape hatch {env} is referenced by "
                    f"{', '.join(referencing[:3])} but none of those "
                    "files carries a differential marker "
                    "(differential / bit-identical / lock-step) — the "
                    "hatch must be proven equivalent, not just toggled")

    @staticmethod
    def _anchor(repo: RepoIndex, env: str) -> Tuple[str, int]:
        """Anchor the finding at the knob's envconf parse site (the
        flag's definition), falling back to example.conf."""
        for relpath in (ENVCONF, "example.conf"):
            sf = repo.get(relpath)
            if sf is None:
                continue
            for i, line in enumerate(sf.lines, 1):
                if env in line:
                    return relpath, i
        return ENVCONF, 1
