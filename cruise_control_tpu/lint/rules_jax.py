"""CCSA001-003: the jax-side invariants — host-sync discipline in the
megastep pump, donation-set exactness, and trace-time purity.

Each of these encodes a contract a prior PR paid for:

- CCSA001: ``run_bounded_pass`` keeps one dispatch in flight; a blocking
  host readback (``float()``/``int()``/``bool()``/``.item()``/
  ``np.asarray``/``.tolist()`` on a device value) inside the pump region
  stalls the pipeline exactly where the overlap is earned, and —
  because AdaptiveDispatch costs dispatches as readback-to-readback
  deltas — double-bills the predecessor's execution into the next
  observation (chain.py's staleness contract, PR 5).
- CCSA002: the donated megastep kernels may donate ONLY the mutable set
  ``{assignment, leader_slot}`` (``strip_mutable``) and the bounded
  pass's own carry ``resume`` (``chain.PassCarry``, made by
  ``start_pass`` for the pass and owned by its pump): every other tensor
  is topology, shared across generations by the incremental model
  pipeline's cache — donating a shared buffer lets XLA delete it under
  the cache's feet (model/refresh.py).
- CCSA003: functions traced by ``lax.while_loop``/``scan``/``cond``/
  ``switch`` run ONCE at trace time; Python mutation of enclosing state
  inside them happens once per compilation, not once per round — the
  silent-wrong-answer class.
"""

from __future__ import annotations

import ast

from .core import Finding, FileContext, Rule, register

# -- shared donation helpers -------------------------------------------------

#: The exact mutable set of the split state (chain.strip_mutable): the two
#: tensors the search rewrites. Everything else is topology.
MUTABLE_SET = ("assignment", "leader_slot")
#: What else a donated kernel may consume: the bounded pass's carry, which
#: no model generation shares (chain.start_pass makes one a pass).
PASS_OWNED = ("resume",)


def _donate_argnums_of(call: ast.Call) -> ast.expr | None:
    """The ``donate_argnums=`` value of a ``jax.jit(...)`` /
    ``partial(jax.jit, ...)`` call expression, else None."""
    for kw in call.keywords:
        if kw.arg in ("donate_argnums", "donate_argnames"):
            return kw.value
    return None


def _const_argnums(value: ast.expr) -> list[object] | None:
    """Literal argnums as a list, or None when not statically resolvable."""
    if isinstance(value, ast.Constant):
        return [value.value]
    if isinstance(value, (ast.Tuple, ast.List)):
        out = []
        for elt in value.elts:
            if not isinstance(elt, ast.Constant):
                return None
            out.append(elt.value)
        return out
    return None


def _positional_params(func: ast.FunctionDef) -> list[str]:
    a = func.args
    return [arg.arg for arg in a.posonlyargs + a.args]


def _is_jit_call(call: ast.Call) -> bool:
    name = Rule.dotted(call.func) or ""
    if name in ("jax.jit", "jit", "pjit", "jax.pjit"):
        return True
    # functools.partial(jax.jit, ...) decorator form
    if name.endswith("partial") and call.args:
        inner = Rule.dotted(call.args[0]) or ""
        return inner in ("jax.jit", "jit", "pjit", "jax.pjit")
    return False


@register
class HostSyncInPumpRule(Rule):
    """CCSA001: no host synchronization inside the async pump or the
    donated chain drivers."""

    rule_id = "CCSA001"
    title = "host-sync leak in the megastep pump / donated drivers"

    #: Files containing the pump machinery. The rule is repo-specific by
    #: design — these are the modules that own the one-behind dispatch
    #: pipelines (single-cluster, sharded, and the fleet megabatch) plus
    #: the direct-assignment transport kernels (round 17: its donated
    #: jits are detected structurally, and any host sync traced into a
    #: sweep body would be a silent per-compile constant). The round-21
    #: sparse-plan kernels ride the same set: the fractional/rounding
    #: planes live in analyzer/direct.py and the mesh rank_stride twins
    #: in parallel/chain_sharded.py — both already pump files, so their
    #: donated forms are regions from the moment they are written.
    PUMP_FILES = ("cruise_control_tpu/analyzer/chain.py",
                  "cruise_control_tpu/analyzer/direct.py",
                  "cruise_control_tpu/parallel/chain_sharded.py",
                  "cruise_control_tpu/fleet/megabatch.py")
    #: Region functions: the pumps themselves, their per-dispatch
    #: ``enqueue`` closures (the megabatch's batched enqueues share the
    #: name, so they are covered structurally), and the async-readback
    #: decode helpers. Donated-jit kernels are detected structurally on
    #: top of this set.
    REGION_FUNCS = ("run_bounded_pass", "run_megabatch_pass", "enqueue",
                    "_chain_infos_from_stats")

    SYNC_BUILTINS = ("float", "int", "bool")
    SYNC_METHODS = ("item", "tolist")
    SYNC_DOTTED = ("np.asarray", "numpy.asarray", "onp.asarray",
                   "jax.device_get")

    def _is_region(self, func: ast.FunctionDef) -> bool:
        if func.name in self.REGION_FUNCS:
            return True
        for dec in func.decorator_list:
            if isinstance(dec, ast.Call) and _donate_argnums_of(dec) \
                    is not None:
                return True
        return False

    def check_file(self, ctx: FileContext) -> list[Finding]:
        if ctx.rel not in self.PUMP_FILES:
            return []
        findings: list[Finding] = []
        for node in ast.walk(ctx.tree):
            if not isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                continue
            if not self._is_region(node):
                continue
            # Walk this region's OWN subtree, skipping nested functions
            # that are themselves regions — they are visited in their own
            # right, so one violation never reports twice.
            stack: list = list(node.body)
            while stack:
                sub = stack.pop()
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                        and self._is_region(sub):
                    continue
                if isinstance(sub, ast.Call):
                    hit = self._sync_kind(sub)
                    if hit is not None:
                        findings.append(Finding(
                            self.rule_id, ctx.rel, sub.lineno,
                            f"`{hit}` in pump region `{node.name}` blocks "
                            "on a device value — stalls the one-behind "
                            "pipeline and double-bills AdaptiveDispatch "
                            "(annotate intentional readbacks: "
                            "`# ccsa: ok[CCSA001] <why here>`)"))
                stack.extend(ast.iter_child_nodes(sub))
        return findings

    def _sync_kind(self, call: ast.Call) -> str | None:
        name = self.dotted(call.func)
        if name in self.SYNC_DOTTED:
            return name
        if name in self.SYNC_BUILTINS and len(call.args) == 1 \
                and not call.keywords \
                and not isinstance(call.args[0], ast.Constant):
            return f"{name}()"
        if isinstance(call.func, ast.Attribute) \
                and call.func.attr in self.SYNC_METHODS \
                and not call.args and not call.keywords:
            return f".{call.func.attr}()"
        return None


@register
class DonationSetRule(Rule):
    """CCSA002: ``donate_argnums`` may only donate the mutable set."""

    rule_id = "CCSA002"
    title = "donation outside the strip_mutable mutable set"

    def check_file(self, ctx: FileContext) -> list[Finding]:
        findings: list[Finding] = []
        defs_by_name: dict[str, list[ast.FunctionDef]] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.FunctionDef):
                defs_by_name.setdefault(node.name, []).append(node)

        decorator_calls: set[int] = set()
        for node in ast.walk(ctx.tree):
            # Decorator form: @partial(jax.jit, donate_argnums=...) /
            # @jax.jit(donate_argnums=...) above a def. The argnums index
            # the DECORATED function's positional parameters.
            if isinstance(node, ast.FunctionDef):
                for dec in node.decorator_list:
                    if isinstance(dec, ast.Call) and _is_jit_call(dec):
                        decorator_calls.add(id(dec))
                        val = _donate_argnums_of(dec)
                        if val is not None:
                            findings.extend(self._verify(
                                ctx, dec, val, _positional_params(node),
                                node.name))
            # Call form: jax.jit(fn_or_shard_map(fn), donate_argnums=...).
            elif isinstance(node, ast.Call) and _is_jit_call(node) \
                    and id(node) not in decorator_calls:
                val = _donate_argnums_of(node)
                if val is None:
                    continue
                params, label = self._resolve_call_target(node, defs_by_name)
                findings.extend(self._verify(ctx, node, val, params, label))
        return findings

    def _resolve_call_target(self, call: ast.Call,
                             defs_by_name: dict[str, list[ast.FunctionDef]],
                             ) -> tuple[list[str] | None, str]:
        """Positional params of the function a jit call wraps. Unwraps
        transform layers (``jax.jit(shard_map(body, ...), ...)``,
        ``jax.jit(jax.vmap(body), ...)``, and stacks thereof — the
        megabatch kernels resolve their donation set THROUGH vmap, which
        maps each donated argument to the same-position parameter of the
        batched body)."""
        target = call.args[0] if call.args else None
        seen = 0
        while isinstance(target, ast.Call) and target.args and seen < 8:
            target = target.args[0]   # vmap(body)/shard_map(body) -> body
            seen += 1
        if isinstance(target, ast.Name):
            cands = defs_by_name.get(target.id, [])
            if len(cands) == 1:
                return _positional_params(cands[0]), target.id
            return None, target.id
        if isinstance(target, ast.Lambda):
            a = target.args
            return [x.arg for x in a.posonlyargs + a.args], "<lambda>"
        return None, self.dotted(target) or "<expr>"

    def _verify(self, ctx: FileContext, at: ast.AST, val: ast.expr,
                params: list[str] | None, label: str) -> list[Finding]:
        nums = _const_argnums(val)
        if nums is None:
            return [Finding(
                self.rule_id, ctx.rel, at.lineno,
                f"donate_argnums of `{label}` is not a literal — the "
                "donation set cannot be verified against the mutable set "
                f"{set(MUTABLE_SET)}")]
        donated: list[str] = []
        for n in nums:
            if isinstance(n, str):
                donated.append(n)     # donate_argnames
            elif isinstance(n, int) and params is not None:
                donated.append(params[n] if n < len(params)
                               else f"<argnum {n}>")
            elif params is None:
                return [Finding(
                    self.rule_id, ctx.rel, at.lineno,
                    f"cannot resolve the function `{label}` donates into "
                    "— donation set unverifiable (donate via a local "
                    "`def` so ccsa can map argnums to parameter names)")]
        bad = [d for d in donated if d not in MUTABLE_SET + PASS_OWNED]
        if not bad:
            return []
        return [Finding(
            self.rule_id, ctx.rel, at.lineno,
            f"`{label}` donates {bad} — only the strip_mutable mutable "
            f"set {set(MUTABLE_SET)} may be donated; topology tensors "
            "are shared across generations by the refresh cache "
            "(model/refresh.py) and a donated shared buffer is deleted "
            "under the cache's feet")]


@register
class TraceTimeSideEffectRule(Rule):
    """CCSA003: no Python mutation of enclosing state inside ``lax``
    body functions."""

    rule_id = "CCSA003"
    title = "trace-time side effect inside a lax body function"

    MUTATORS = ("append", "extend", "add", "update", "insert", "pop",
                "popitem", "remove", "discard", "clear", "setdefault",
                "appendleft", "extendleft")
    _OPS = {"while_loop": (0, 1), "scan": (0,), "cond": (1, 2),
            "fori_loop": (2,)}

    def check_file(self, ctx: FileContext) -> list[Finding]:
        lax_names = self._lax_imports(ctx.tree)
        defs_by_name: dict[str, list[ast.FunctionDef]] = {}
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.FunctionDef):
                defs_by_name.setdefault(node.name, []).append(node)

        findings: list[Finding] = []
        seen: set[int] = set()
        for call in ast.walk(ctx.tree):
            if not isinstance(call, ast.Call):
                continue
            op = self._lax_op(call, lax_names)
            if op is None:
                continue
            bodies: list[ast.AST] = []
            if op == "switch":
                if len(call.args) >= 2 and isinstance(
                        call.args[1], (ast.List, ast.Tuple)):
                    bodies.extend(call.args[1].elts)
            else:
                for idx in self._OPS[op]:
                    if idx < len(call.args):
                        bodies.append(call.args[idx])
            for body in bodies:
                fn = self._resolve(body, defs_by_name)
                if fn is None or id(fn) in seen:
                    continue
                seen.add(id(fn))
                findings.extend(self._check_body(ctx, fn, op))
        return findings

    @staticmethod
    def _lax_imports(tree: ast.Module) -> set[str]:
        names: set[str] = set()
        for node in ast.walk(tree):
            if isinstance(node, ast.ImportFrom) \
                    and (node.module or "").endswith("lax"):
                names.update(a.asname or a.name for a in node.names)
        return names

    def _lax_op(self, call: ast.Call, lax_names: set[str]) -> str | None:
        name = self.dotted(call.func)
        if name is None:
            return None
        head, _, last = name.rpartition(".")
        if last not in self._OPS and last != "switch":
            return None
        if head.endswith("lax") or (not head and name in lax_names):
            return last
        return None

    @staticmethod
    def _resolve(body: ast.AST,
                 defs_by_name: dict[str, list[ast.FunctionDef]],
                 ) -> ast.AST | None:
        if isinstance(body, ast.Lambda):
            return body
        if isinstance(body, ast.Name):
            cands = defs_by_name.get(body.id, [])
            if len(cands) == 1:
                return cands[0]
        # Calls producing bodies (e.g. branch(i) factories) and foreign
        # references are out of reach for a single-file walk.
        return None

    def _check_body(self, ctx: FileContext, fn: ast.AST,
                    op: str) -> list[Finding]:
        findings: list[Finding] = []

        def flag(node: ast.AST, what: str) -> None:
            findings.append(Finding(
                self.rule_id, ctx.rel, node.lineno,
                f"{what} inside a `lax.{op}` body function runs ONCE at "
                "trace time, not once per iteration — thread it through "
                "the carry instead (silent-wrong-answer class)"))

        def check_scope(scope: ast.AST, bound: frozenset) -> None:
            """Per-scope walk: ``bound`` accumulates names local to this
            scope or an enclosing one INSIDE the traced body — a nested
            helper's own bindings never leak outward, so a name it
            rebinds stays free (and flaggable) in the outer scope."""
            bound = bound | self.own_assigned_names(scope)
            stack = list(scope.body) if not isinstance(scope, ast.Lambda) \
                else [scope.body]
            while stack:
                node = stack.pop()
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                     ast.Lambda)):
                    check_scope(node, bound)
                    continue
                if isinstance(node, (ast.Global, ast.Nonlocal)):
                    flag(node, f"`{type(node).__name__.lower()}` rebinding")
                elif isinstance(node, ast.Call) \
                        and isinstance(node.func, ast.Attribute) \
                        and node.func.attr in self.MUTATORS \
                        and isinstance(node.func.value, ast.Name) \
                        and node.func.value.id not in bound:
                    flag(node, f"mutation `{node.func.value.id}"
                               f".{node.func.attr}(...)` of enclosing "
                               "state")
                elif isinstance(node, (ast.Assign, ast.AugAssign)):
                    targets = node.targets if isinstance(node, ast.Assign) \
                        else [node.target]
                    for t in targets:
                        if isinstance(t, (ast.Subscript, ast.Attribute)) \
                                and isinstance(t.value, ast.Name) \
                                and t.value.id not in bound:
                            flag(node, f"write through enclosing name "
                                       f"`{t.value.id}`")
                stack.extend(ast.iter_child_nodes(node))
            return None

        check_scope(fn, frozenset())
        return findings
