"""determinism-seam: all time and randomness flows through the seams.

The deterministic simulator (``swarmkit_tpu/sim``) replays the whole
control plane under a virtual clock and seeded id source; that only
works because production code reads wall-clock time through
``models.types.now()`` and mints randomness/ids through injected
``random.Random`` seams / ``utils.identity``.  This rule flags the
bypasses that silently break seed-reproducibility:

* ``time.time()`` / ``time.monotonic()`` calls — use
  ``models.types.now()`` (``time.perf_counter`` is allowed: it measures
  durations for metrics and never steers control flow);
* ``random.Random()`` with no seed, and module-level ``random.*``
  draws from the global unseeded RNG — inject a ``random.Random(seed)``
  (the ``rng or random.Random()`` constructor-default idiom for an
  injected seam parameter is allowed);
* ``uuid.uuid4()`` — use ``utils.identity.new_id()`` (routes through
  the sim's ``set_id_source`` seam);
* ``os.urandom()`` — use ``utils.identity.new_secret()`` unless the
  bytes are cryptographic key material (suppress with a justification
  in that case).

Whitelisted modules are the seams themselves, the virtual clock, the
real-subprocess executor (wall-clock health timers are its point),
crypto (``security/``), and host-side tooling (``scripts/``) that
measures real time on purpose.
"""

from __future__ import annotations

import ast
from typing import Iterable, List

from ..core import Checker, Finding, ImportMap, ModuleInfo, parent_of, \
    register

ALLOWED_PATHS = (
    "swarmkit_tpu/models/types.py",    # defines the now() seam
    "swarmkit_tpu/sim/clock.py",       # the virtual clock implementation
    "swarmkit_tpu/utils/identity.py",  # the id seam (crypto source)
    "swarmkit_tpu/agent/procexec.py",  # real subprocesses, real deadlines
    "swarmkit_tpu/agent/testutils.py",
    "swarmkit_tpu/security/",          # cert validity / key material are
                                       # real-world crypto by definition
    "scripts/",
)

_BANNED_CALLS = {
    "time.time":
        "bare wall-clock read; route through models.types.now() so the "
        "sim's virtual clock controls it",
    "time.monotonic":
        "bare monotonic read; route deadlines through models.types.now()"
        " (or take an injected clock seam)",
    "uuid.uuid4":
        "unseamed id; use utils.identity.new_id() (respects the sim's "
        "set_id_source seam)",
    "os.urandom":
        "unseamed entropy; use utils.identity.new_secret(), or suppress "
        "with a justification if this is cryptographic key material",
}

# module-level draws from the global, unseeded RNG
_RANDOM_GLOBAL_FNS = {"random", "randint", "uniform", "choice", "shuffle",
                      "randrange", "sample", "betavariate", "gauss"}

# numpy's global-RNG twins (ISSUE 15: the learned-scorer strategy made
# numpy arrays a production data path — weight loading must read the
# checked-in artifact, NEVER fall back to a random init; device kernels
# must not mint noise outside an injected seeded Generator)
_NUMPY_GLOBAL_FNS = {"rand", "randn", "randint", "random", "choice",
                     "shuffle", "permutation", "normal", "uniform",
                     "seed"}


def _is_or_default(node: ast.Call) -> bool:
    """True for the injected-seam constructor-default idiom
    ``self._rng = rng or random.Random()`` — the fallback only fires in
    production, where nondeterminism is the correct behavior."""
    p = parent_of(node)
    return isinstance(p, ast.BoolOp) and isinstance(p.op, ast.Or) \
        and p.values and p.values[-1] is node


@register
class DeterminismSeam(Checker):
    name = "determinism-seam"
    description = ("time/randomness/ids must flow through the injected "
                   "seams (models.types.now, utils.identity, rng params)")

    def check(self, mod: ModuleInfo) -> Iterable[Finding]:
        if any(mod.relpath.startswith(p) for p in ALLOWED_PATHS):
            return ()
        imports = ImportMap(mod.tree)
        out: List[Finding] = []
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = imports.resolve(node.func)
            if dotted is None:
                continue
            if dotted in _BANNED_CALLS:
                out.append(mod.finding(
                    self.name, node, f"{dotted}(): {_BANNED_CALLS[dotted]}"))
            elif dotted == "random.Random" and not node.args \
                    and not node.keywords and not _is_or_default(node):
                out.append(mod.finding(
                    self.name, node,
                    "random.Random() with no seed: inject a seeded rng "
                    "(Agent(rng=...) style) or seed explicitly"))
            elif dotted.startswith("random.") \
                    and dotted.split(".", 1)[1] in _RANDOM_GLOBAL_FNS:
                out.append(mod.finding(
                    self.name, node,
                    f"{dotted}() draws from the global unseeded RNG; use "
                    "an injected random.Random(seed)"))
            elif dotted == "numpy.random.default_rng" and not node.args \
                    and not node.keywords:
                out.append(mod.finding(
                    self.name, node,
                    "numpy.random.default_rng() with no seed: pass an "
                    "explicit seed (learned-scorer weights load from the "
                    "checked-in artifact, never a random init)"))
            elif dotted.startswith("numpy.random.") \
                    and dotted.rsplit(".", 1)[1] in _NUMPY_GLOBAL_FNS:
                out.append(mod.finding(
                    self.name, node,
                    f"{dotted}() draws from numpy's global RNG; use a "
                    "seeded numpy.random.default_rng(seed) (and never "
                    "random-init scorer weights)"))
        return out
