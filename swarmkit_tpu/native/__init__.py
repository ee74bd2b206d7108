"""Native hot-path loader.

Compiles hotpath.c on first use (cached as an in-place .so next to the
source) and falls back to the pure-Python implementations when compilation
or import fails — the package never *requires* the toolchain.  Set
SWARMKIT_TPU_NO_NATIVE=1 to force the Python paths (used by differential
tests that pit the two implementations against each other).

Staleness: ``build.py`` stamps the sha256 of ``hotpath.c`` next to the
.so; ``get()`` rebuilds before importing whenever the stamp disagrees
with the current source, so an edited hotpath.c can never be served by a
stale prebuilt module (scripts/ci_check.sh enforces the same hash).

The columnar commit plane (binary block raft entries, native decode /
follower apply / watch fan-out) has its own escape hatch on top:
``SWARM_NATIVE_COMMIT=0`` routes it to the pure-Python oracle paths —
same breaker discipline as the device planner.  ``get_commit()`` is the
accessor those call sites use; when the native module is unavailable
while the commit plane is *not* explicitly disabled, each call counts a
``swarm_native_commit_fallbacks`` tick so a measured window can prove
the native path actually ran (benchmark/retreat.py and chip_smoke.py
count any growth as a retreat).
"""

from __future__ import annotations

import hashlib
import logging
import os
import subprocess
import sys

log = logging.getLogger("native")

_mod = None
_tried = False


def _source_stale() -> bool:
    """True when the in-place .so predates the current hotpath.c (or
    has no stamp at all — pre-stamp builds)."""
    here = os.path.dirname(os.path.abspath(__file__))
    stamp = os.path.join(here, "_hotpath.src.sha256")
    try:
        with open(stamp) as f:
            recorded = f.read().strip()
        with open(os.path.join(here, "hotpath.c"), "rb") as f:
            current = hashlib.sha256(f.read()).hexdigest()
    except OSError:
        return True
    return recorded != current


def _rebuild() -> bool:
    here = os.path.dirname(os.path.abspath(__file__))
    try:
        subprocess.run(
            [sys.executable, os.path.join(here, "build.py")],
            check=True, capture_output=True, timeout=300, cwd=here)
        return True
    except Exception as e:  # toolchain missing, etc. — run pure-Python
        log.warning("native hotpath build failed (%s); using Python "
                    "paths", e)
        return False


def get():
    """Return the _hotpath C module, or None when unavailable/disabled."""
    global _mod, _tried
    if os.environ.get("SWARMKIT_TPU_NO_NATIVE"):
        return None
    if _tried:
        return _mod
    _tried = True
    if _source_stale() and not _rebuild():
        # a stale .so would serve old semantics for new source — worse
        # than the Python fallback, which is always current
        _mod = None
        return _mod
    try:
        from . import _hotpath as m  # type: ignore[attr-defined]
        _mod = m
        return _mod
    except ImportError:
        pass
    # fresh stamp but no importable .so (e.g. a clean checkout whose
    # stamp survived while build artifacts are gitignored): build once
    if _rebuild():
        try:
            from . import _hotpath as m  # type: ignore[attr-defined]
            _mod = m
            return _mod
        except ImportError as e:
            log.warning("native hotpath unavailable (%s); using Python "
                        "paths", e)
    _mod = None
    return _mod


def commit_enabled() -> bool:
    """The columnar-commit-plane escape hatch, read per call so tests
    can flip it without reimporting."""
    return os.environ.get("SWARM_NATIVE_COMMIT", "1") != "0"


def get_commit():
    """The native module for the columnar commit plane (block decode,
    follower apply, watch fan-out), or None when disabled
    (``SWARM_NATIVE_COMMIT=0``) or unavailable.  An unavailable-but-
    requested native plane counts a fallback tick per call — the
    evidence that a measured window really ran native."""
    if not commit_enabled():
        return None
    mod = get()
    if mod is None:
        from ..utils.metrics import registry as _metrics
        _metrics.counter("swarm_native_commit_fallbacks")
    return mod
