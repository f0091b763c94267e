"""Jit-discipline audit (BNG010/BNG011/BNG012).

Every `jax.jit` site in the tree is audited for the three retrace/
donation hazards that have actually bitten TPU dataplanes like this one:

* **BNG010 — uncached jit construction.** A `jax.jit(...)` call inside
  a plain function body builds a NEW jitted callable (and its trace
  cache) per invocation. Step factories must be module-level or
  `functools.lru_cache`d (the engine's `_pipeline_jit`/`_dhcp_jit`
  pattern: the cache is keyed on geometry so engines with one shape
  share one compile).

* **BNG011 — missing donation on a program that threads the tables.** A
  jitted program that applies host table deltas (`apply_fastpath_updates`,
  `apply_nat_updates`, `apply_update`, `apply_qupdate`, ...: the mesh
  loop's step in its body, and the engine's two packet-free apply
  programs, which ARE such a function under `jax.jit`), or whose body
  runs a step's entry (`pipeline_step`, `dhcp_fastpath`,
  `express_verdicts`: since PR 50 no one-chip step applies a delta),
  threads the device tables through itself; without `donate_argnums`
  the old table buffers stay live across the call and HBM holds two
  copies of every table — the ROADMAP perf campaign's "donation/layout
  audit of the jitted step" as a repeatable pass.

* **BNG012 — per-batch Python scalar as a traced argument.** Calling a
  jitted step with a bare `int(...)`/`float(...)`/arithmetic scalar
  traces it at weak type; int-vs-float drift between call sites (or an
  accidental static annotation) retraces per batch. The codebase
  convention is fixed-width wrapping at the call site
  (`np.uint32(int(now))`), which BNG012 enforces. An unhashable value
  in `static_argnums` position is the same bug's other face and is
  flagged when the static arg is a literal list/dict.
"""

from __future__ import annotations

import ast

from bng_tpu.analysis.core import (Finding, Pass, Project, call_name,
                                   dotted, enclosing_function, scope_of)

APPLY_FNS = {"apply_fastpath_updates", "apply_nat_updates", "apply_update",
             "apply_qupdate", "_apply_all_updates", "apply_all_updates"}
# the AOT-compiled express entry (ops/express.py): a jitted step whose
# body runs the express probe program threads (and must donate) the
# dhcp chain AND the descriptor batch — the program's output verdict
# block aliases the descriptor staging buffer, so an undonated express
# step silently doubles both the table HBM and the per-dispatch
# allocation (ISSUE 13). Recognized like the apply fns: donation is
# required even if a refactor ever drops the in-step update apply
# (PR 50 did, for every one-chip step: the fused and the DHCP-only
# program's entries stand beside it, for the same reason).
EXPRESS_ENTRY_FNS = {"express_verdicts", "pipeline_step", "dhcp_fastpath"}
CACHE_DECORATORS = {"lru_cache", "cache"}
# jitted-step callables at call sites (the engine/scheduler convention).
# `express_exe` is the AOT-compiled express executable (the engine's
# run_express_aot parameter name): same scalar discipline at call sites
# — an AOT executable rejects nothing at trace time (there is none), so
# a weak-typed scalar would surface as a shape error at dispatch.
STEP_CALLEES = {"_step", "_dhcp_step", "step_fn", "express_exe"}


def _is_jax_jit(node: ast.Call) -> tuple[bool, ast.Call | None]:
    """(is a jit site, the call carrying the jit kwargs).

    Handles `jax.jit(f, ...)` and `functools.partial(jax.jit, ...)`."""
    d = dotted(node.func)
    if d in ("jax.jit", "jit"):
        return True, node
    if d.endswith("partial") and node.args:
        if dotted(node.args[0]) in ("jax.jit", "jit"):
            return True, node
    return False, None


def _has_cache_decorator(fn: ast.FunctionDef) -> bool:
    for dec in fn.decorator_list:
        d = dotted(dec.func if isinstance(dec, ast.Call) else dec)
        if d.rsplit(".", 1)[-1] in CACHE_DECORATORS:
            return True
    return False


def _kwarg(node: ast.Call, name: str) -> ast.AST | None:
    for kw in node.keywords:
        if kw.arg == name:
            return kw.value
    return None


class JitDisciplinePass(Pass):
    name = "jit-discipline"
    description = ("jit factories cached, table steps donated, traced "
                   "scalars fixed-width")
    codes = {
        "BNG010": "jax.jit constructed inside an uncached function "
                  "(retrace/recompile per call)",
        "BNG011": "table-updating jitted step without donate_argnums",
        "BNG012": "bare Python scalar / unhashable static at a jitted "
                  "call site",
    }

    def run(self, project: Project) -> list[Finding]:
        out: list[Finding] = []
        for sf in project.files:
            out.extend(self._check_file(sf.path, sf.tree))
        return out

    def _check_file(self, path: str, tree: ast.Module):
        for node in ast.walk(tree):
            if isinstance(node, ast.Call):
                is_jit, jit_call = _is_jax_jit(node)
                if is_jit:
                    yield from self._check_jit_site(path, node, jit_call)
                yield from self._check_step_call(path, node)
            elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                for dec in node.decorator_list:
                    if (isinstance(dec, (ast.Name, ast.Attribute))
                            and dotted(dec) in ("jax.jit", "jit")):
                        yield from self._check_bare_jit(path, dec, node)

    # -- BNG010 / BNG011 -------------------------------------------------

    def _check_jit_site(self, path: str, node: ast.Call,
                        jit_call: ast.Call):
        scope = scope_of(node)
        # `@functools.partial(jax.jit, ...)` / `@jax.jit` decorating a
        # function: the construction site IS the decorated function's
        # scope, and the decorated function is the jitted body
        parent = getattr(node, "_bng_parent", None)
        decorated = (parent if isinstance(
            parent, (ast.FunctionDef, ast.AsyncFunctionDef))
            and node in parent.decorator_list else None)
        fn = (enclosing_function(decorated) if decorated is not None
              else enclosing_function(node))
        if fn is not None and not _has_cache_decorator(fn):
            # constructed inside a function body: cached factory or bust
            yield Finding(
                "BNG010", path, node.lineno,
                f"jax.jit constructed inside `{fn.name}` without "
                f"functools.lru_cache — a new trace cache per call "
                f"(the `_pipeline_jit` factory pattern is the fix)",
                scope=scope, detail=f"jit-in-{fn.name}")
        # donation audit: does the jitted function apply table updates?
        target = None
        if decorated is not None:
            inner = decorated
        else:
            target = jit_call.args[1] if (dotted(jit_call.func).endswith(
                "partial") and len(jit_call.args) > 1) else (
                jit_call.args[0] if jit_call.args else None)
            inner = self._resolve_local_fn(node, target)
        must_donate = APPLY_FNS | EXPRESS_ENTRY_FNS
        applies = False
        if isinstance(target, ast.Name) and target.id in APPLY_FNS:
            # an apply function jitted as it stands (the engine's packet-
            # free programs: `jax.jit(_apply_all_updates, donate_...)`)
            applies = True
        elif inner is not None:
            applies = any(isinstance(n, ast.Call)
                          and call_name(n) in must_donate
                          for n in ast.walk(inner))
        elif fn is not None:
            # factory whose inner fn we couldn't chase (shard_map wrap):
            # any sibling local function applying updates counts
            applies = any(
                isinstance(s, ast.FunctionDef) and any(
                    isinstance(n, ast.Call) and call_name(n) in must_donate
                    for n in ast.walk(s))
                for s in ast.walk(fn))
        if applies:
            donate = (_kwarg(jit_call, "donate_argnums")
                      or _kwarg(jit_call, "donate_argnames"))
            if donate is None:
                yield Finding(
                    "BNG011", path, node.lineno,
                    "jitted step applies table updates (or runs the "
                    "express probe program) but has no donate_argnums — "
                    "the pre-step table buffers stay live and HBM holds "
                    "every table twice",
                    scope=scope, detail="missing-donate")
        # unhashable static args
        for kw_name in ("static_argnums", "static_argnames"):
            v = _kwarg(jit_call, kw_name)
            if isinstance(v, (ast.List, ast.Dict, ast.Set)):
                yield Finding(
                    "BNG012", path, node.lineno,
                    f"{kw_name} given a literal {type(v).__name__} — "
                    f"static argnums must be hashable tuples",
                    scope=scope, detail=f"unhashable-{kw_name}")

    def _check_bare_jit(self, path: str, dec: ast.AST,
                        decorated: ast.FunctionDef):
        """`@jax.jit` with no call parentheses — an ast.Attribute/Name,
        invisible to the Call walk above. Same BNG010 rule (construction
        happens when the enclosing function body runs), and BNG011 is
        unconditional on a table-applying body: the bare form cannot
        carry donate_argnums at all."""
        scope = scope_of(dec)
        fn = enclosing_function(decorated)
        if fn is not None and not _has_cache_decorator(fn):
            yield Finding(
                "BNG010", path, dec.lineno,
                f"jax.jit constructed inside `{fn.name}` without "
                f"functools.lru_cache — a new trace cache per call "
                f"(the `_pipeline_jit` factory pattern is the fix)",
                scope=scope, detail=f"jit-in-{fn.name}")
        if any(isinstance(n, ast.Call)
               and call_name(n) in (APPLY_FNS | EXPRESS_ENTRY_FNS)
               for n in ast.walk(decorated)):
            yield Finding(
                "BNG011", path, dec.lineno,
                "jitted step applies table updates (or runs the "
                "express probe program) but has no donate_argnums — "
                "the pre-step table buffers stay live and HBM holds "
                "every table twice",
                scope=scope, detail="missing-donate")

    @staticmethod
    def _resolve_local_fn(site: ast.AST, target: ast.AST | None):
        """Chase a Name/Lambda jit target to a local FunctionDef."""
        if isinstance(target, ast.Lambda):
            return target
        if not isinstance(target, ast.Name):
            return None
        fn = enclosing_function(site)
        space = fn.body if fn is not None else []
        for stmt in space:
            if (isinstance(stmt, ast.FunctionDef)
                    and stmt.name == target.id):
                return stmt
        return None

    # -- BNG012 at step call sites ---------------------------------------

    def _check_step_call(self, path: str, node: ast.Call):
        if call_name(node) not in STEP_CALLEES:
            return
        if not isinstance(node.func, ast.Attribute):
            return
        scope = scope_of(node)
        for i, arg in enumerate(node.args):
            bad = None
            if (isinstance(arg, ast.Call)
                    and isinstance(arg.func, ast.Name)
                    and arg.func.id in ("int", "float")):
                bad = f"{arg.func.id}(...)"
            elif isinstance(arg, ast.BinOp):
                bad = "arithmetic expression"
            elif (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, (int, float))
                    and not isinstance(arg.value, bool)):
                bad = repr(arg.value)
            if bad is not None:
                yield Finding(
                    "BNG012", path, arg.lineno,
                    f"bare Python scalar ({bad}) as traced arg {i} of a "
                    f"jitted step — wrap it fixed-width at the call site "
                    f"(np.uint32(...)/np.float32(...)) or weak-type "
                    f"drift retraces per batch",
                    scope=scope, detail=f"scalar-arg-{i}")
