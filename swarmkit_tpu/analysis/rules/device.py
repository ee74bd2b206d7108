"""device-path-purity: no host syncs or debug hooks inside plan fns.

The planner's throughput story (async dispatch overlapping host commit,
PR 4) dies the moment a jitted plan fn — or a helper it calls — forces
a host round-trip.  Inside device-path functions in ``ops``/``parallel``
(any function reaching jit: decorated with ``@jax.jit`` /
``functools.partial(jax.jit, ...)``, wrapped via ``jax.jit(fn)``, or
called from one within the same module) this rule flags:

* ``.item()`` / ``float(tracer)`` / ``int(tracer)`` — implicit D2H
  syncs (literal-constant args are fine);
* ``jax.device_get`` / ``.block_until_ready()`` — explicit syncs that
  belong in the *fetch* stage (``ops/kernel.py fetch_plan``), never
  inside the compiled program;
* ``np.*`` — numpy ops silently fall back to the host; device code uses
  ``jnp``;
* ``jax.debug.*`` — debug callbacks in the hot path recompile and
  serialize the program.

The streaming scheduler's resident device state (ops/streaming.py,
ISSUE 14) adds the DONATION shapes: a jit program built with
``donate_argnums`` hands its input buffers to XLA — the old array
object is dead the moment the call dispatches.  In the HOST drivers of
the same modules this rule therefore also flags **reuse of a donated
buffer after dispatch**: an argument passed at a donated position of a
donating jitted callable that is read again later in the same function
without being rebound from the call's result.  (The companion hazard —
a host read of a resident array *inside* the program — is the np./
.item() class above and already fires.)

The device-telemetry ledger (obs/devicetelemetry.py, ISSUE 18) adds the
UNACCOUNTED TRANSFER shape in the host drivers: every H2D staged with
``jax.device_put`` and every ``.block_until_ready()`` fetch sync in a
host function of these modules must flow through the device ledger — a
transfer the ledger never sees is a byte stream no reading of
``/debug/device`` or chip_smoke.py can account for.  A host function
touching those seams passes only
when its body also carries an accounting call (``note_h2d`` /
``note_d2h`` / ``note_bytes_avoided``, or any dotted call through
``devicetelemetry``).

The mesh-native resident tier (ISSUE 19) adds the CROSS-SHARD shapes
in the host drivers: the sharded fused pipeline keeps its carry and
resident columns laid out across the mesh between chunk dispatches, so

* a **mid-chunk ``jax.device_get``** — a value fetched D2H and then
  passed onward to a device dispatcher later in the same function —
  round-trips the sharded carry through the host between chunks
  (gather + re-lay-out across every shard) instead of fetching once
  after the last dispatch;
* a **re-``device_put`` of an already-resident array** — re-staging a
  name that is itself bound from a prior ``jax.device_put`` — pays a
  full cross-mesh re-lay-out for an array the devices already hold.

Other host-side driver code in the same modules (``TPUPlanner``, the
``ShardedPlanFn`` padding wrapper) is untouched: syncs are its job —
but transfers must be counted.
"""

from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set

from ..core import Checker, Finding, ImportMap, ModuleInfo, register

SCOPE_PREFIXES = ("swarmkit_tpu/ops/", "swarmkit_tpu/parallel/")

_SYNC_ATTRS = {"item", "block_until_ready"}

#: a host fn carrying any of these calls is "accounted": the transfer
#: seams it touches report into the device ledger
_ACCOUNT_ATTRS = {"note_h2d", "note_d2h", "note_bytes_avoided"}


def _is_accounted(fn: ast.FunctionDef) -> bool:
    """True when the function body carries a device-ledger accounting
    call — an ``_ACCOUNT_ATTRS`` attr call (works for the conventional
    ``_devtel`` alias) or any dotted call through ``devicetelemetry``."""
    for node in ast.walk(fn):
        if not isinstance(node, ast.Call):
            continue
        if isinstance(node.func, ast.Attribute) \
                and node.func.attr in _ACCOUNT_ATTRS:
            return True
        d = _dotted(node.func)
        if d and "devicetelemetry" in d:
            return True
    return False


def _is_jit_decorator(dec: ast.AST, imports: ImportMap) -> bool:
    """Matches @jax.jit, @jit, @functools.partial(jax.jit, ...) and
    @partial(jit, ...)."""
    if isinstance(dec, ast.Call):
        dotted = imports.resolve(dec.func)
        if dotted in ("jax.jit", "jit"):
            return True
        if dotted in ("functools.partial", "partial") and dec.args:
            return imports.resolve(dec.args[0]) in ("jax.jit", "jit")
        return False
    return imports.resolve(dec) in ("jax.jit", "jit")


def _donated_positions(call: ast.Call) -> Optional[Set[int]]:
    """Donated arg positions from a ``jax.jit``/``partial(jax.jit, …)``
    call's ``donate_argnums`` keyword; None when absent/unparsable."""
    for kw in call.keywords:
        if kw.arg != "donate_argnums":
            continue
        v = kw.value
        if isinstance(v, ast.Constant) and isinstance(v.value, int):
            return {v.value}
        if isinstance(v, (ast.Tuple, ast.List)) and all(
                isinstance(e, ast.Constant) and isinstance(e.value, int)
                for e in v.elts):
            return {e.value for e in v.elts}
        return None
    return None


def _dotted(node: ast.AST) -> Optional[str]:
    """Dotted source form of a Name/Attribute chain ("self.cpu_dev"),
    None for anything fancier."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if not isinstance(node, ast.Name):
        return None
    parts.append(node.id)
    return ".".join(reversed(parts))


def _module_functions(tree: ast.AST) -> Dict[str, ast.FunctionDef]:
    """Module-level and class-level defs by (unqualified) name."""
    out: Dict[str, ast.FunctionDef] = {}
    stack = list(ast.iter_child_nodes(tree))
    while stack:
        node = stack.pop()
        if isinstance(node, ast.ClassDef):
            stack.extend(ast.iter_child_nodes(node))
        elif isinstance(node, ast.FunctionDef):
            out.setdefault(node.name, node)
    return out


@register
class DevicePathPurity(Checker):
    name = "device-path-purity"
    description = ("no .item()/float()/np./jax.debug host syncs inside "
                   "jitted plan fns (ops/, parallel/)")

    def check(self, mod: ModuleInfo) -> Iterable[Finding]:
        if not mod.relpath.startswith(SCOPE_PREFIXES):
            return ()
        imports = ImportMap(mod.tree)
        fns = _module_functions(mod.tree)

        # roots: jit-decorated defs + fns wrapped as `x = jax.jit(f)`
        device: Set[str] = set()
        for name, fn in fns.items():
            if any(_is_jit_decorator(d, imports) for d in fn.decorator_list):
                device.add(name)
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Call) \
                    and imports.resolve(node.func) == "jax.jit" \
                    and node.args and isinstance(node.args[0], ast.Name) \
                    and node.args[0].id in fns:
                device.add(node.args[0].id)

        # closure: helpers called (by bare name) from device fns, within
        # this module, are device code too
        frontier = list(device)
        while frontier:
            fn = fns.get(frontier.pop())
            if fn is None:
                continue
            for sub in ast.walk(fn):
                if isinstance(sub, ast.Call) \
                        and isinstance(sub.func, ast.Name) \
                        and sub.func.id in fns \
                        and sub.func.id not in device:
                    device.add(sub.func.id)
                    frontier.append(sub.func.id)

        out: List[Finding] = []
        for name in sorted(device):
            out.extend(self._check_fn(mod, fns[name], imports))

        # ---- donation discipline in the HOST drivers: collect the
        # module's donating jitted callables, then flag any donated
        # buffer read again after dispatch without a rebind
        donating: Dict[str, Set[int]] = {}
        for fn_name, fn in fns.items():
            for dec in fn.decorator_list:
                if isinstance(dec, ast.Call) \
                        and _is_jit_decorator(dec, imports):
                    pos = _donated_positions(dec)
                    if pos:
                        donating[fn_name] = pos
        for node in ast.walk(mod.tree):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and imports.resolve(node.value.func) in ("jax.jit",
                                                            "jit"):
                pos = _donated_positions(node.value)
                if pos:
                    for tgt in node.targets:
                        if isinstance(tgt, ast.Name):
                            donating[tgt.id] = pos
        if donating:
            for fn in fns.values():
                out.extend(self._check_donation_reuse(mod, fn, donating))

        # ---- transfer accounting in the HOST drivers: device_put /
        # block_until_ready outside the telemetry-wrapped seams is a
        # byte stream the device ledger (and every regression gate
        # keyed on it) never sees
        for name, fn in fns.items():
            if name in device:
                continue   # device fns: the sync shapes above own these
            out.extend(self._check_unaccounted_transfer(
                mod, fn, imports))

        # ---- cross-shard discipline in the HOST drivers (ISSUE 19):
        # mid-chunk D2H of a value still being dispatched, and re-puts
        # of arrays a prior device_put already made resident
        dispatchers = device | set(donating)
        for name, fn in fns.items():
            if name in device:
                continue
            out.extend(self._check_cross_shard(
                mod, fn, imports, dispatchers))
        return out

    def _check_cross_shard(self, mod: ModuleInfo, fn: ast.FunctionDef,
                           imports: ImportMap,
                           dispatchers: Set[str]) -> List[Finding]:
        """One host function: flag ``jax.device_get(x)`` where the same
        dotted ``x`` is passed to a device dispatcher (a jitted or
        donating callable of this module) on a LATER line — the sharded
        carry is round-tripping through the host mid-chunk — and flag
        ``jax.device_put`` of a name bound from a prior ``device_put``
        — the array is already device-resident and the re-put re-lays
        it out across the whole mesh."""
        out: List[Finding] = []
        dispatch_arg_lines: Dict[str, List[int]] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Name) \
                    and node.func.id in dispatchers:
                for a in node.args:
                    d = _dotted(a)
                    if d:
                        dispatch_arg_lines.setdefault(d, []).append(
                            node.lineno)
        for node in ast.walk(fn):
            if isinstance(node, ast.Call) \
                    and imports.resolve(node.func) == "jax.device_get" \
                    and node.args:
                d = _dotted(node.args[0])
                if d and any(ln > node.lineno
                             for ln in dispatch_arg_lines.get(d, ())):
                    out.append(mod.finding(
                        self.name, node,
                        f"mid-chunk jax.device_get of {d!r} in host fn "
                        f"{fn.name}: the value feeds a device dispatch "
                        "below — keep the sharded carry device-resident "
                        "between chunks and fetch once, after the last "
                        "dispatch"))
        resident: Dict[str, int] = {}
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) \
                    and isinstance(node.value, ast.Call) \
                    and imports.resolve(node.value.func) \
                    == "jax.device_put":
                for tgt in node.targets:
                    if isinstance(tgt, ast.Name):
                        resident[tgt.id] = node.lineno
        if resident:
            for node in ast.walk(fn):
                if isinstance(node, ast.Call) \
                        and imports.resolve(node.func) \
                        == "jax.device_put" \
                        and node.args:
                    d = _dotted(node.args[0])
                    if d in resident and node.lineno > resident[d]:
                        out.append(mod.finding(
                            self.name, node,
                            f"re-device_put of already-resident {d!r} "
                            f"in host fn {fn.name}: staged at line "
                            f"{resident[d]} — reuse the resident "
                            "handle (a sharded column re-put re-lays "
                            "out the whole mesh)"))
        return out

    def _check_unaccounted_transfer(self, mod: ModuleInfo,
                                    fn: ast.FunctionDef,
                                    imports: ImportMap) -> List[Finding]:
        """One host function: collect its ``jax.device_put`` calls
        (direct or via a local ``put = jax.device_put`` alias) and its
        ``.block_until_ready()`` syncs; all pass when the body carries
        an accounting call, all fire when it does not."""
        aliases: Set[str] = set()
        for node in ast.walk(fn):
            if isinstance(node, ast.Assign) \
                    and imports.resolve(node.value) == "jax.device_put":
                aliases.update(t.id for t in node.targets
                               if isinstance(t, ast.Name))
        puts: List[ast.Call] = []
        syncs: List[ast.Call] = []
        for node in ast.walk(fn):
            if not isinstance(node, ast.Call):
                continue
            if imports.resolve(node.func) == "jax.device_put" \
                    or (isinstance(node.func, ast.Name)
                        and node.func.id in aliases):
                puts.append(node)
            elif isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "block_until_ready":
                syncs.append(node)
        if not (puts or syncs) or _is_accounted(fn):
            return []
        out: List[Finding] = []
        for node in puts:
            out.append(mod.finding(
                self.name, node,
                f"unaccounted transfer: jax.device_put in host fn "
                f"{fn.name} with no device-ledger accounting — note "
                "the staged bytes (obs.devicetelemetry.note_h2d) or "
                "route through an accounted seam"))
        for node in syncs:
            out.append(mod.finding(
                self.name, node,
                f"unaccounted transfer: .block_until_ready() in host "
                f"fn {fn.name} with no device-ledger accounting — "
                "note the fetch (obs.devicetelemetry.note_d2h) or "
                "fetch via ops/kernel.py fetch_plan"))
        return out

    def _check_donation_reuse(self, mod: ModuleInfo,
                              fn: ast.FunctionDef,
                              donating: Dict[str, Set[int]]
                              ) -> List[Finding]:
        """Lexical donated-buffer-reuse scan over one (host) function:
        for every call to a donating jitted callable, any read of a
        donated argument below the call — with no intervening rebind —
        is a dead buffer being consumed."""
        out: List[Finding] = []
        loads: Dict[str, List[int]] = {}
        stores: Dict[str, List[int]] = {}
        for node in ast.walk(fn):
            d = _dotted(node) if isinstance(
                node, (ast.Name, ast.Attribute)) else None
            if d is None:
                continue
            ctx = getattr(node, "ctx", None)
            if isinstance(ctx, ast.Store):
                stores.setdefault(d, []).append(node.lineno)
            elif isinstance(ctx, ast.Load):
                loads.setdefault(d, []).append(node.lineno)
        for node in ast.walk(fn):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Name)
                    and node.func.id in donating):
                continue
            call_end = getattr(node, "end_lineno", None) or node.lineno
            for p in donating[node.func.id]:
                if p >= len(node.args) or any(
                        isinstance(a, ast.Starred)
                        for a in node.args[:p + 1]):
                    continue   # starred unpacking: positions unknowable
                d = _dotted(node.args[p])
                if d is None:
                    continue   # subscript/call args: not tracked
                for load_line in loads.get(d, ()):
                    if load_line <= call_end:
                        continue   # the call's own argument lines
                    if any(node.lineno <= s <= load_line
                           for s in stores.get(d, ())):
                        continue   # rebound from the result: fine
                    out.append(mod.finding(
                        self.name, node,
                        f"donated buffer {d!r} (arg {p} of "
                        f"{node.func.id}) read again at line "
                        f"{load_line} after dispatch: donation hands "
                        "the buffer to XLA — rebind it from the "
                        "call's result"))
                    break
        return out

    def _check_fn(self, mod: ModuleInfo, fn: ast.FunctionDef,
                  imports: ImportMap) -> List[Finding]:
        out: List[Finding] = []
        numpy_aliases = {alias for alias, target in imports.aliases.items()
                         if target == "numpy"}
        for node in ast.walk(fn):
            if isinstance(node, ast.Call):
                dotted = imports.resolve(node.func)
                tail = node.func.attr \
                    if isinstance(node.func, ast.Attribute) else None
                if tail in _SYNC_ATTRS:
                    out.append(mod.finding(
                        self.name, node,
                        f".{tail}() inside device fn {fn.name}: implicit "
                        "host sync; keep values on device (fetch "
                        "belongs in ops/kernel.py fetch_plan)"))
                elif dotted == "jax.device_get":
                    out.append(mod.finding(
                        self.name, node,
                        f"jax.device_get inside device fn {fn.name}: "
                        "D2H belongs in the fetch stage, not the "
                        "compiled program"))
                elif dotted and dotted.startswith("jax.debug."):
                    out.append(mod.finding(
                        self.name, node,
                        f"{dotted} inside device fn {fn.name}: debug "
                        "callbacks serialize the hot path; gate or "
                        "remove"))
                elif isinstance(node.func, ast.Name) \
                        and node.func.id in ("float", "int") \
                        and node.args \
                        and not isinstance(node.args[0], ast.Constant) \
                        and not (isinstance(node.args[0], ast.Name)
                                 and node.args[0].id.isupper()):
                    out.append(mod.finding(
                        self.name, node,
                        f"{node.func.id}() on a traced value inside "
                        f"device fn {fn.name}: implicit host sync; use "
                        "jnp dtype casts"))
            elif isinstance(node, ast.Attribute) \
                    and isinstance(node.value, ast.Name) \
                    and node.value.id in numpy_aliases:
                out.append(mod.finding(
                    self.name, node,
                    f"np.{node.attr} inside device fn {fn.name}: numpy "
                    "runs on host; use jnp"))
        return out
