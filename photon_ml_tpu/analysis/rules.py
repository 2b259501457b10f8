"""photonlint rule catalog (PH001–PH013).

Each rule is a class with an `rule_id`, a one-line `summary` (the `--list-
rules` catalog), and `check(ctx) -> Iterable[Finding]` over an
`engine.ModuleContext`.  Adding a rule = adding a class here and listing
it in `all_rules()`; fixtures under tests/lint_fixtures/ demonstrate one
violation and one compliant near-miss per rule.

PH010–PH013 are PROGRAM rules (`program_rule = True`,
`check_program(ProgramContext)`): the concurrency pass in
`analysis/concurrency.py` needs the whole package at once — a call graph,
thread roots, and the lock-acquisition-order graph are interprocedural by
nature.  `engine.lint_paths` runs them after the per-module rules.

Precision over recall: every check is anchored to the module semantics the
engine resolved (import aliases, wrapper forms, device-value tracking), so
a finding is worth reading.  What a rule cannot see statically (values
flowing through unannotated call results, factory-returned solvers) it
stays silent on — the compile-count and parity tests remain the backstop
for those.
"""
from __future__ import annotations

import ast
from typing import Dict, Iterable, List, Optional, Set

from photon_ml_tpu.analysis.engine import (
    DeviceTracker, Finding, ModuleContext, comprehension_device_names,
    iter_function_defs,
)

#: expression contexts that are static under a jit trace: touching a
#: traced value through these never retraces
_STATIC_ATTRS = ("shape", "ndim", "dtype", "size", "nbytes")


class Rule:
    rule_id = "PH000"
    name = "rule"
    summary = ""

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        raise NotImplementedError


def _contained_defs(root) -> Set[ast.AST]:
    """All function defs lexically inside `root` (including root)."""
    return {n for n in ast.walk(root)
            if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef,
                              ast.Lambda))}


# -- PH001: host sync in hot-path modules -------------------------------------

class HostSyncRule(Rule):
    rule_id = "PH001"
    name = "host-sync"
    summary = ("float()/bool()/int()/.item()/np.asarray/jax.device_get on "
               "device values in hot-path modules outside flush points")

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        if not ctx.is_hot_path:
            return []
        findings: List[Finding] = []
        skip: Set[ast.AST] = set()
        for fn, info in ctx.traced_defs.items():
            skip |= _contained_defs(fn)  # traced code can't host-sync
        for fn in iter_function_defs(ctx.tree):
            if ctx.flush_point(fn):
                skip |= _contained_defs(fn)

        def scan_scope(body, seed_fn=None):
            tracker = DeviceTracker(ctx)
            if seed_fn is not None:
                tracker.seed_params(seed_fn)

            def on_expr(expr):
                extra = comprehension_device_names(tracker, expr) \
                    if isinstance(expr, (ast.GeneratorExp, ast.ListComp,
                                         ast.SetComp, ast.DictComp)) else set()
                added = extra - tracker.device
                tracker.device |= added
                try:
                    for node in ast.walk(expr):
                        if isinstance(node, (ast.GeneratorExp, ast.ListComp,
                                             ast.SetComp, ast.DictComp)) \
                                and node is not expr:
                            on_expr(node)
                            continue
                        if isinstance(node, ast.Call):
                            self._check_call(ctx, tracker, node, findings)
                finally:
                    tracker.device -= added

            tracker.walk(body, on_expr)

        scan_scope(ctx.tree.body)
        for fn in iter_function_defs(ctx.tree):
            if fn in skip:
                continue
            scan_scope(fn.body, seed_fn=fn)
        return findings

    def _check_call(self, ctx, tracker, node, findings) -> None:
        func = node.func
        if isinstance(func, ast.Name) and func.id in ("float", "int",
                                                      "bool"):
            if func.id not in ctx.names and len(node.args) == 1 \
                    and tracker.is_device_expr(node.args[0]):
                findings.append(ctx.finding(
                    self.rule_id, node,
                    f"{func.id}() on a device value forces a blocking "
                    "device->host sync — defer to the iteration's batched "
                    "flush point"))
            return
        if isinstance(func, ast.Name) and func.id == "range":
            if any(tracker.is_device_expr(a) for a in node.args):
                findings.append(ctx.finding(
                    self.rule_id, node,
                    "range() over a device value syncs via implicit "
                    "__index__ — fetch the bound once at a flush point"))
            return
        if isinstance(func, ast.Attribute) and func.attr in ("item",
                                                             "tolist"):
            if not node.args and tracker.is_device_expr(func.value):
                findings.append(ctx.finding(
                    self.rule_id, node,
                    f".{func.attr}() on a device value forces a blocking "
                    "device->host sync — defer to the batched flush point"))
            return
        origin = ctx.resolve(func)
        if origin in ("numpy.asarray", "numpy.array") and node.args \
                and tracker.is_device_expr(node.args[0]):
            findings.append(ctx.finding(
                self.rule_id, node,
                f"{origin}() on a device value is a hidden device->host "
                "transfer — keep it device-resident or fetch at a flush "
                "point"))
            return
        if origin == "jax.device_get":
            findings.append(ctx.finding(
                self.rule_id, node,
                "jax.device_get outside a whitelisted flush point — hot "
                "paths batch ALL readbacks into one flush per outer "
                "iteration (mark a designated flush with "
                "`# photonlint: flush-point`)"))


# -- PH002: retrace hazards ---------------------------------------------------

class RetraceHazardRule(Rule):
    rule_id = "PH002"
    name = "retrace-hazard"
    summary = ("Python branches / format strings on traced values inside "
               "jit/vmap-wrapped functions; non-hashable static args at "
               "call sites of jitted callables")

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        findings: List[Finding] = []
        for fn, info in ctx.traced_defs.items():
            if isinstance(fn, ast.Lambda):
                continue  # a lambda body has no statements to branch in
            args = fn.args
            traced = {a.arg for a in (args.posonlyargs + args.args
                                      + args.kwonlyargs)}
            traced -= info.static_names
            self._scan_body(ctx, fn.body, set(traced), findings)
        self._check_call_sites(ctx, findings)
        return findings

    # names loaded from `expr` through a NON-static context
    def _traced_loads(self, expr, traced: Set[str]) -> List[ast.Name]:
        out: List[ast.Name] = []

        def visit(node):
            if isinstance(node, ast.Attribute) \
                    and node.attr in _STATIC_ATTRS:
                return  # x.shape / x.dtype ... resolve at trace time
            if isinstance(node, ast.Call) and isinstance(node.func,
                                                         ast.Name) \
                    and node.func.id in ("len", "isinstance", "type"):
                return
            if isinstance(node, ast.Compare) and all(
                    isinstance(op, (ast.Is, ast.IsNot))
                    for op in node.ops):
                return  # `x is None` is a static structural test
            if isinstance(node, ast.Name) and isinstance(node.ctx,
                                                         ast.Load) \
                    and node.id in traced:
                out.append(node)
                return
            for child in ast.iter_child_nodes(node):
                visit(child)

        visit(expr)
        return out

    def _scan_body(self, ctx, body, traced: Set[str], findings) -> None:
        for stmt in body:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                continue  # lax.cond/while_loop bodies: traced separately
            if isinstance(stmt, (ast.If, ast.While)):
                loads = self._traced_loads(stmt.test, traced)
                if loads:
                    kind = "if" if isinstance(stmt, ast.If) else "while"
                    findings.append(ctx.finding(
                        self.rule_id, stmt.test,
                        f"Python `{kind}` on traced value "
                        f"`{loads[0].id}` inside a jit-wrapped function — "
                        "resolves at trace time and retraces per distinct "
                        "value (use lax.cond/jnp.where, or mark the "
                        "argument static)"))
                self._scan_format_exprs(ctx, stmt.test, traced, findings)
                self._scan_body(ctx, stmt.body, traced, findings)
                self._scan_body(ctx, stmt.orelse, traced, findings)
                continue
            if isinstance(stmt, (ast.For, ast.With, ast.Try)):
                for e in DonationSafetyRule._stmt_exprs(stmt):
                    self._scan_format_exprs(ctx, e, traced, findings)
                for b in DonationSafetyRule._stmt_bodies(stmt):
                    self._scan_body(ctx, b, traced, findings)
                continue
            if isinstance(stmt, ast.Assign):
                if self._traced_loads(stmt.value, traced):
                    for tgt in stmt.targets:
                        for n in ast.walk(tgt):
                            if isinstance(n, ast.Name):
                                traced.add(n.id)
                else:
                    for tgt in stmt.targets:
                        if isinstance(tgt, ast.Name):
                            traced.discard(tgt.id)
            self._scan_format_exprs(ctx, stmt, traced, findings)

    def _scan_format_exprs(self, ctx, root, traced: Set[str],
                           findings) -> None:
        for node in ast.walk(root):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue  # nested def contents handled by their own trace
            if isinstance(node, ast.JoinedStr):
                loads = []
                for v in node.values:
                    if isinstance(v, ast.FormattedValue):
                        loads += self._traced_loads(v.value, traced)
                if loads:
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        f"f-string formats traced value "
                        f"`{loads[0].id}` inside a jit-wrapped "
                        "function — forces trace-time concretization "
                        "(format at the call site instead)"))
            elif isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "format":
                loads = [l for a in node.args
                         for l in self._traced_loads(a, traced)]
                if loads:
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        f".format() on traced value `{loads[0].id}` "
                        "inside a jit-wrapped function"))

    def _check_call_sites(self, ctx, findings) -> None:
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            info = ctx.callable_info(node.func)
            if info is None or not (info.static_positions
                                    or info.static_names):
                continue
            for i, arg in enumerate(node.args):
                if i in info.static_positions and isinstance(
                        arg, (ast.List, ast.Dict, ast.Set)):
                    findings.append(ctx.finding(
                        self.rule_id, arg,
                        "non-hashable literal passed in a static argument "
                        "position of a jitted callable — raises or "
                        "retraces every call (pass a tuple)"))
            for kw in node.keywords:
                if kw.arg in info.static_names and isinstance(
                        kw.value, (ast.List, ast.Dict, ast.Set)):
                    findings.append(ctx.finding(
                        self.rule_id, kw.value,
                        f"non-hashable literal for static argument "
                        f"`{kw.arg}` of a jitted callable — raises or "
                        "retraces every call (pass a tuple)"))


# -- PH003: donation safety ---------------------------------------------------

class DonationSafetyRule(Rule):
    rule_id = "PH003"
    name = "donation-safety"
    summary = ("read of a variable after it was passed in a "
               "donate_argnums position (the buffer is dead — donate a "
               "copy or rebind the result)")

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        findings: List[Finding] = []
        self._scan_scope(ctx, ctx.tree.body, findings)
        for fn in iter_function_defs(ctx.tree):
            self._scan_scope(ctx, fn.body, findings)
        return findings

    def _scan_scope(self, ctx, body, findings) -> None:
        donated: Dict[str, str] = {}  # name -> callee description

        def scan_expr(expr):
            for node in ast.iter_child_nodes(expr):
                scan_expr(node)
            if isinstance(expr, ast.Name) and isinstance(expr.ctx,
                                                         ast.Load) \
                    and expr.id in donated:
                findings.append(ctx.finding(
                    self.rule_id, expr,
                    f"`{expr.id}` is read after being donated to "
                    f"{donated[expr.id]} — the buffer was invalidated; "
                    "donate an explicit copy (jnp full-extent slices "
                    "ALIAS) or rebind before reuse"))
                del donated[expr.id]  # one finding per donation
            elif isinstance(expr, ast.Call):
                info = ctx.callable_info(expr.func)
                if info is None or not (info.donate_positions
                                        or info.donate_names):
                    return
                callee = (expr.func.id if isinstance(expr.func, ast.Name)
                          else getattr(expr.func, "attr", "a jitted "
                                       "callable"))
                for i, arg in enumerate(expr.args):
                    if i in info.donate_positions and isinstance(arg,
                                                                 ast.Name):
                        donated[arg.id] = f"`{callee}` (arg {i})"
                for kw in expr.keywords:
                    if kw.arg in info.donate_names and isinstance(
                            kw.value, ast.Name):
                        donated[kw.value.id] = f"`{callee}` ({kw.arg}=)"

        def scan_stmt(stmt):
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef)):
                return
            if isinstance(stmt, ast.Assign):
                scan_expr(stmt.value)
                for tgt in stmt.targets:
                    for n in ast.walk(tgt):
                        if isinstance(n, ast.Name):
                            donated.pop(n.id, None)
                return
            if isinstance(stmt, ast.AugAssign):
                scan_expr(stmt.value)
                if isinstance(stmt.target, ast.Name):
                    # x += 1 both reads (flag) and rebinds (clear)
                    if stmt.target.id in donated:
                        findings.append(ctx.finding(
                            self.rule_id, stmt.target,
                            f"`{stmt.target.id}` is read after being "
                            f"donated to {donated[stmt.target.id]}"))
                    donated.pop(stmt.target.id, None)
                return
            for child_expr in self._stmt_exprs(stmt):
                scan_expr(child_expr)
            for child_body in self._stmt_bodies(stmt):
                for s in child_body:
                    scan_stmt(s)

        for stmt in body:
            scan_stmt(stmt)

    @staticmethod
    def _stmt_exprs(stmt):
        if isinstance(stmt, (ast.Return, ast.Expr)) and stmt.value:
            yield stmt.value
        elif isinstance(stmt, (ast.If, ast.While)):
            yield stmt.test
        elif isinstance(stmt, ast.For):
            yield stmt.iter
        elif isinstance(stmt, ast.With):
            for item in stmt.items:
                yield item.context_expr
        elif isinstance(stmt, ast.AnnAssign) and stmt.value:
            yield stmt.value
        elif isinstance(stmt, ast.Raise) and stmt.exc:
            yield stmt.exc

    @staticmethod
    def _stmt_bodies(stmt):
        for field in ("body", "orelse", "finalbody"):
            b = getattr(stmt, field, None)
            if isinstance(b, list):
                yield b
        for h in getattr(stmt, "handlers", ()):
            yield h.body


# -- PH004: fault-site discipline ---------------------------------------------

class FaultSiteRule(Rule):
    rule_id = "PH004"
    name = "fault-site"
    summary = ("faults.fire() sites must be string literals declared in "
               "utils.faults.SITES with declared context keys; the "
               "registry must match the module docs")

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        findings: List[Finding] = []
        registry = getattr(ctx, "sites_registry", {})
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            origin = ctx.resolve(node.func)
            if origin is None or not (origin.endswith(".faults.fire")
                                      or origin == "faults.fire"):
                continue
            if not node.args:
                continue
            site_arg = node.args[0]
            if not (isinstance(site_arg, ast.Constant)
                    and isinstance(site_arg.value, str)):
                findings.append(ctx.finding(
                    self.rule_id, site_arg,
                    "dynamic fault-site name — sites must be string "
                    "literals so injection plans, docs, and greps agree"))
                continue
            site = site_arg.value
            if registry and site not in registry:
                known = ", ".join(sorted(registry))
                findings.append(ctx.finding(
                    self.rule_id, site_arg,
                    f"undeclared fault site {site!r} — declare it in "
                    f"utils.faults.SITES (known: {known})"))
                continue
            declared = set(registry.get(site, ()))
            for kw in node.keywords:
                if kw.arg is not None and registry \
                        and kw.arg not in declared:
                    findings.append(ctx.finding(
                        self.rule_id, kw.value,
                        f"context key {kw.arg!r} is not declared for "
                        f"site {site!r} in utils.faults.SITES "
                        f"(declared: {sorted(declared)}) — injection "
                        "specs matching on it would silently never fire"))
        findings.extend(self._check_registry_docs(ctx, registry))
        return findings

    def _check_registry_docs(self, ctx, registry) -> List[Finding]:
        """When linting the registry module itself: every declared site
        must appear in the module docstring (the operator-facing doc)."""
        if ctx.path != getattr(ctx, "sites_registry_path", None):
            return []
        doc = ast.get_docstring(ctx.tree) or ""
        sites_node = next(
            (n for n in ctx.tree.body
             if isinstance(n, (ast.Assign, ast.AnnAssign))
             and any(isinstance(t, ast.Name) and t.id == "SITES"
                     for t in (n.targets if isinstance(n, ast.Assign)
                               else [n.target]))), None)
        if sites_node is None:
            return []
        missing = sorted(s for s in registry if s not in doc)
        if not missing:
            return []
        return [ctx.finding(
            self.rule_id, sites_node,
            f"SITES entries missing from the module docstring: "
            f"{', '.join(missing)} — the registry and the docs must "
            "agree")]


# -- PH005: durable writes ----------------------------------------------------

class DurableWriteRule(Rule):
    rule_id = "PH005"
    name = "durable-write"
    summary = ("checkpoint/model-io modules must write through "
               "utils.durable atomic+fsync helpers, never bare "
               "open(..., 'w')/json.dump")

    _WRITE_MODES = ("w", "a", "x")

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        if not ctx.is_durable_module or ctx.is_durable_impl:
            return []
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Name) and node.func.id == "open" \
                    and node.func.id not in ctx.names:
                mode = None
                if len(node.args) >= 2 and isinstance(node.args[1],
                                                      ast.Constant):
                    mode = node.args[1].value
                for kw in node.keywords:
                    if kw.arg == "mode" and isinstance(kw.value,
                                                       ast.Constant):
                        mode = kw.value.value
                if isinstance(mode, str) and mode.startswith(
                        self._WRITE_MODES):
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        f"bare open(..., {mode!r}) in a durable module — "
                        "a crash mid-write tears the file; use "
                        "utils.durable.atomic_write_text/_json/"
                        "write_marker"))
                continue
            origin = ctx.resolve(node.func)
            if origin == "json.dump":
                findings.append(ctx.finding(
                    self.rule_id, node,
                    "bare json.dump in a durable module — use "
                    "utils.durable.atomic_write_json (tmp + fsync + "
                    "atomic replace)"))
        return findings


# -- PH006: nondeterminism in traced/gated paths ------------------------------

class NondeterminismRule(Rule):
    rule_id = "PH006"
    name = "nondeterminism"
    summary = ("time.*/random.*/np.random.* inside jit/vmap-wrapped "
               "functions — traced once, frozen forever, and parity "
               "gates can't reproduce the trace")

    _TIME = {"time.time", "time.perf_counter", "time.monotonic",
             "time.time_ns", "time.perf_counter_ns",
             "datetime.datetime.now", "datetime.datetime.utcnow"}

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        findings: List[Finding] = []
        for fn in ctx.traced_defs:
            for node in ast.walk(fn):
                if not isinstance(node, ast.Call):
                    continue
                origin = ctx.resolve(node.func)
                if origin is None:
                    continue
                if origin in self._TIME:
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        f"{origin}() inside a jit-wrapped function — the "
                        "value freezes at trace time; take timestamps on "
                        "the host around the compiled call"))
                elif origin.startswith(("random.", "numpy.random.")):
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        f"{origin}() inside a jit-wrapped function — "
                        "host RNG freezes at trace time and breaks "
                        "parity-gated reproducibility; thread a "
                        "jax.random key instead"))
        return findings


# -- PH007: raw span timing in hot-path modules -------------------------------

class RawTimerRule(Rule):
    rule_id = "PH007"
    name = "raw-timer"
    summary = ("raw time.perf_counter() span timing in hot-path modules — "
               "route through telemetry (PhaseTimings.span/blocked or "
               "telemetry.timings.clock) so every phase lands in ONE "
               "trace, not a private stopwatch")

    _TIMERS = ("time.perf_counter", "time.perf_counter_ns")

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        # telemetry/ is the sanctioned implementation and is not a
        # hot-path directory, so it is exempt by scoping
        if not ctx.is_hot_path:
            return []
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            origin = ctx.resolve(node.func)
            if origin in self._TIMERS:
                findings.append(ctx.finding(
                    self.rule_id, node,
                    f"{origin}() span timing in a hot-path module — time "
                    "phases through telemetry (PhaseTimings.span / "
                    ".blocked, or telemetry.timings.clock) so the span "
                    "lands in the unified trace instead of a bespoke "
                    "counter nothing else can correlate"))
        return findings


# -- PH008: telemetry event-registry drift ------------------------------------

class EventRegistryRule(Rule):
    rule_id = "PH008"
    name = "event-registry"
    summary = ("every utils.faults.SITES name and telemetry.flight."
               "TRIGGERS name needs a telemetry event constant in "
               "telemetry/events.py (and vice versa — stale entries "
               "fail too); flight.trigger() reasons must be literal "
               "registered names")

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        findings: List[Finding] = []
        events = getattr(ctx, "events_registry", {}) or {}
        triggers = getattr(ctx, "triggers_registry", {}) or {}
        sites = getattr(ctx, "sites_registry", {}) or {}
        # registry diffs are reported ON the registry modules, so the
        # finding lands where the fix goes
        if ctx.path == getattr(ctx, "sites_registry_path", None):
            findings.extend(self._registry_diff(
                ctx, "SITES", set(sites) - set(events)))
        if ctx.path == getattr(ctx, "triggers_registry_path", None):
            findings.extend(self._registry_diff(
                ctx, "TRIGGERS", set(triggers) - set(events)))
        if ctx.path == getattr(ctx, "events_registry_path", None):
            stale = set(events) - set(sites) - set(triggers)
            if stale:
                node = self._dict_node(ctx, "EVENTS")
                if node is not None:
                    findings.append(ctx.finding(
                        self.rule_id, node,
                        f"stale telemetry event constant(s) "
                        f"{sorted(stale)}: no fault site or flight "
                        "trigger of that name exists — remove them (or "
                        "register the site/trigger)"))
        findings.extend(self._check_trigger_calls(ctx, triggers))
        return findings

    @staticmethod
    def _dict_node(ctx, var_name: str):
        for node in ctx.tree.body:
            targets = (node.targets if isinstance(node, ast.Assign)
                       else [node.target]
                       if isinstance(node, ast.AnnAssign) else [])
            if any(isinstance(t, ast.Name) and t.id == var_name
                   for t in targets):
                return node
        return None

    def _registry_diff(self, ctx, var_name: str, missing) -> List[Finding]:
        if not missing:
            return []
        node = self._dict_node(ctx, var_name)
        if node is None:
            return []
        return [ctx.finding(
            self.rule_id, node,
            f"{var_name} name(s) {sorted(missing)} have no telemetry "
            "event constant — operators grep traces and flight bundles "
            "by event name, so declare each in telemetry/events.py "
            "EVENTS before the registry entry lands")]

    def _check_trigger_calls(self, ctx, triggers) -> List[Finding]:
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            origin = ctx.resolve(node.func)
            if origin is None or not (origin.endswith(".flight.trigger")
                                      or origin == "flight.trigger"):
                continue
            if not node.args:
                continue
            reason = node.args[0]
            if not (isinstance(reason, ast.Constant)
                    and isinstance(reason.value, str)):
                findings.append(ctx.finding(
                    self.rule_id, reason,
                    "dynamic flight-trigger reason — triggers must be "
                    "string literals registered in telemetry.flight."
                    "TRIGGERS so the dump taxonomy, docs, and greps "
                    "agree (suppress forwarding sites that re-fire an "
                    "already-validated reason)"))
                continue
            if triggers and reason.value not in triggers:
                findings.append(ctx.finding(
                    self.rule_id, reason,
                    f"unregistered flight trigger {reason.value!r} — "
                    "declare it in telemetry.flight.TRIGGERS (known: "
                    f"{', '.join(sorted(triggers))})"))
        return findings


# -- PH014: multi-writer discipline in multi-process modules ------------------

class MultiprocessWriteRule(Rule):
    """Every process of a multi-host run executes the modules listed in
    `engine.MULTIPROCESS_MODULE_SUFFIXES` — an unguarded write there runs
    P times against ONE path (torn summaries, racing prunes, doubled
    registry entries).  The utils.durable helpers self-guard (no-op off
    process 0 unless `all_process=True`), so they are compliant by
    construction; everything that BYPASSES them must either sit under a
    lexical primary guard (`multihost.is_primary()` /
    `process_index() == 0`, including the early-return form) or carry a
    `# photonlint: all-process` annotation declaring the multi-writer
    intent (per-process files, race-tolerant sweeps).  A durable.* call
    that passes `all_process=True` disables the helper's own guard, so it
    needs the annotation too."""

    rule_id = "PH014"
    name = "multiprocess-write"
    summary = ("multi-process-reachable modules: bare durable writes and "
               "destructive mutations must be process-0-guarded "
               "(multihost.is_primary() / process_index() == 0) or "
               "annotated `# photonlint: all-process`; durable.* calls "
               "passing all_process=True need the annotation as well")

    _WRITE_MODES = ("w", "a", "x")
    _DESTRUCTIVE = {"json.dump", "numpy.save", "numpy.savez",
                    "numpy.savez_compressed", "shutil.rmtree",
                    "shutil.copyfile", "shutil.move", "os.remove",
                    "os.unlink", "os.replace", "os.rename"}
    _DURABLE_PKG = "photon_ml_tpu.utils.durable."

    def check(self, ctx: ModuleContext) -> Iterable[Finding]:
        if not ctx.is_multiprocess_module or ctx.is_durable_impl:
            return []
        parents: Dict[ast.AST, ast.AST] = {}
        for node in ast.walk(ctx.tree):
            for child in ast.iter_child_nodes(node):
                parents[child] = node
        findings: List[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            kind = self._classify(ctx, node)
            if kind is None:
                continue
            if node.lineno in ctx.suppressions.all_process_lines:
                continue
            if self._primary_guarded(ctx, parents, node):
                continue
            if kind == "override":
                findings.append(ctx.finding(
                    self.rule_id, node,
                    "durable.* call passes all_process=True — that "
                    "disables the helper's primary-only multi-writer "
                    "guard, so EVERY process writes; annotate the line "
                    "`# photonlint: all-process` to make the per-process "
                    "intent reviewable (or drop the override)"))
            else:
                findings.append(ctx.finding(
                    self.rule_id, node,
                    f"unguarded {kind} in a multi-process module — every "
                    "process executes this line against the same path; "
                    "guard it with multihost.is_primary() (process 0 owns "
                    "durable artifacts) or annotate `# photonlint: "
                    "all-process` for a deliberately per-process / "
                    "race-tolerant write"))
        return findings

    # -- classification -------------------------------------------------------
    def _classify(self, ctx: ModuleContext, node: ast.Call) -> Optional[str]:
        """'override' for durable.*(all_process=True), a description
        string for a bare write/mutation, None for anything benign."""
        if (isinstance(node.func, ast.Name) and node.func.id == "open"
                and node.func.id not in ctx.names):
            mode = None
            if len(node.args) >= 2 and isinstance(node.args[1],
                                                  ast.Constant):
                mode = node.args[1].value
            for kw in node.keywords:
                if kw.arg == "mode" and isinstance(kw.value, ast.Constant):
                    mode = kw.value.value
            if isinstance(mode, str) and mode.startswith(self._WRITE_MODES):
                return f"open(..., {mode!r}) write"
            return None
        origin = ctx.resolve(node.func)
        if origin is None:
            return None
        if origin in self._DESTRUCTIVE:
            return f"{origin}() call"
        if origin.startswith(self._DURABLE_PKG):
            for kw in node.keywords:
                if (kw.arg == "all_process"
                        and isinstance(kw.value, ast.Constant)
                        and kw.value.value is True):
                    return "override"
        return None

    # -- lexical primary-guard resolution -------------------------------------
    @staticmethod
    def _callee_tail(n: ast.Call) -> str:
        f = n.func
        if isinstance(f, ast.Attribute):
            return f.attr
        if isinstance(f, ast.Name):
            return f.id
        return ""

    def _primary_test(self, test: ast.AST) -> bool:
        """True when `test` asserts this IS the primary process: an
        is_primary() call anywhere in it, or process_index() == 0."""
        for n in ast.walk(test):
            if isinstance(n, ast.Call) \
                    and self._callee_tail(n) == "is_primary":
                return True
            if (isinstance(n, ast.Compare) and len(n.ops) == 1
                    and isinstance(n.ops[0], ast.Eq)):
                sides = [n.left] + n.comparators
                if (any(isinstance(s, ast.Constant) and s.value == 0
                        for s in sides)
                        and any(isinstance(s, ast.Call)
                                and self._callee_tail(s) == "process_index"
                                for s in sides)):
                    return True
        return False

    def _negated_primary_test(self, test: ast.AST) -> bool:
        """True when `test` asserts this is NOT the primary:
        `not is_primary()` / `process_index() != 0`."""
        if isinstance(test, ast.UnaryOp) and isinstance(test.op, ast.Not):
            return self._primary_test(test.operand)
        if (isinstance(test, ast.Compare) and len(test.ops) == 1
                and isinstance(test.ops[0], ast.NotEq)):
            sides = [test.left] + test.comparators
            return (any(isinstance(s, ast.Constant) and s.value == 0
                        for s in sides)
                    and any(isinstance(s, ast.Call)
                            and self._callee_tail(s) == "process_index"
                            for s in sides))
        return False

    def _primary_guarded(self, ctx: ModuleContext,
                         parents: Dict[ast.AST, ast.AST],
                         node: ast.AST) -> bool:
        cur = node
        while cur in parents:
            par = parents[cur]
            if isinstance(par, ast.If):
                in_body = any(cur is s for s in par.body)
                if in_body and self._primary_test(par.test):
                    return True
                # else-branch of an `if not primary:` split
                if (not in_body and any(cur is s for s in par.orelse)
                        and self._negated_primary_test(par.test)):
                    return True
            elif isinstance(par, (ast.FunctionDef, ast.AsyncFunctionDef)):
                # early-return form: a preceding top-level statement of
                # the function reads `if not is_primary(): return` —
                # everything AFTER it is primary-only
                for stmt in par.body:
                    if stmt is cur:
                        break
                    if (isinstance(stmt, ast.If)
                            and self._negated_primary_test(stmt.test)
                            and all(isinstance(s, (ast.Return, ast.Raise))
                                    for s in stmt.body)
                            and not stmt.orelse):
                        return True
            cur = par
        return False


def all_rules() -> List[Rule]:
    from photon_ml_tpu.analysis.concurrency import concurrency_rules
    return [HostSyncRule(), RetraceHazardRule(), DonationSafetyRule(),
            FaultSiteRule(), DurableWriteRule(), NondeterminismRule(),
            RawTimerRule(), EventRegistryRule(),
            MultiprocessWriteRule()] + concurrency_rules()
