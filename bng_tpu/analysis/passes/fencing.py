"""Device-time fencing (BNG050) — no timing of async dispatches without
a force.

The gray-failure class that let three bench rounds publish CPU numbers
as TPU headlines (VERDICT r5, PR 5 postmortem): a wall-clock window
around an ASYNC jitted dispatch measures enqueue cost, not device time.
The telemetry design rule is explicit — device time comes only from
a profiler trace or a window that contains its own force.

The pass finds function-local timing windows:

    t1 = time.perf_counter()          # origin
    ... statements ...
    lat = time.perf_counter() - t1    # close

and flags windows that contain a dispatch to one of the async step
surfaces (`_step`, `_dhcp_step`, `_dispatch_step`, `_run_dhcp_batch`,
`dispatch_scheduled_bulk`, `submit`/`poll`, `process_ring_pipelined`)
but no fence (`block_until_ready`, `device_get`, `np.asarray`,
`flush`/`flush_pipeline`/`quiesce`, `.item`).
Synchronous surfaces (`process`, `process_dhcp`, `process_ring`) force
their own outputs and are not dispatch hazards.
"""

from __future__ import annotations

import ast

from bng_tpu.analysis.core import Finding, Pass, Project, call_name, dotted

CLOCK_CALLS = {"time.time", "time.perf_counter", "time.perf_counter_ns",
               "time.monotonic", "perf_counter", "perf_counter_ns",
               "monotonic"}
ASYNC_DISPATCH = {"_step", "_dhcp_step", "_dispatch_step",
                  "_run_dhcp_batch", "dispatch_scheduled_bulk",
                  "submit", "poll", "process_ring_pipelined", "step_fn"}
FENCES = {"block_until_ready", "device_get", "asarray", "array", "item",
          "flush", "flush_pipeline", "quiesce",
          "drain_completions_blocking", "wait"}


def _clock_call(node: ast.AST) -> bool:
    return (isinstance(node, ast.Call)
            and dotted(node.func) in CLOCK_CALLS)


class FencingPass(Pass):
    name = "fencing"
    description = ("wall-clock windows over async dispatches must "
                   "contain a force/fence")
    codes = {
        "BNG050": "timing window over an async device dispatch without "
                  "block_until_ready or another force",
    }

    def run(self, project: Project) -> list[Finding]:
        out: list[Finding] = []
        for sf in project.files:
            for node in ast.walk(sf.tree):
                if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                    out.extend(self._check_fn(sf, node))
        return out

    def _check_fn(self, sf, fn: ast.FunctionDef):
        stmts = self._flat_statements(fn)
        origins: dict[str, int] = {}  # clock var -> stmt index
        for idx, stmt in enumerate(stmts):
            if (isinstance(stmt, ast.Assign) and len(stmt.targets) == 1
                    and isinstance(stmt.targets[0], ast.Name)
                    and _clock_call(stmt.value)):
                origins[stmt.targets[0].id] = idx
                continue
            for var, start in list(origins.items()):
                if self._closes_window(stmt, var):
                    yield from self._check_window(
                        sf, fn, stmts[start + 1: idx + 1], stmt.lineno, var)
                    origins.pop(var, None)

    @staticmethod
    def _flat_statements(fn: ast.FunctionDef) -> list[ast.stmt]:
        """Statement stream in source order, descending into compound
        bodies (a window often opens before a loop and closes after)."""
        out: list[ast.stmt] = []

        def walk(body):
            for s in body:
                out.append(s)
                for attr in ("body", "orelse", "finalbody"):
                    inner = getattr(s, attr, None)
                    if inner:
                        walk(inner)
                for h in getattr(s, "handlers", ()):
                    walk(h.body)

        walk(fn.body)
        return out

    @staticmethod
    def _closes_window(stmt: ast.stmt, var: str) -> bool:
        """Does this statement compute `time.X() - var`?"""
        for node in ast.walk(stmt):
            if (isinstance(node, ast.BinOp) and isinstance(node.op, ast.Sub)
                    and isinstance(node.right, ast.Name)
                    and node.right.id == var
                    and _clock_call(node.left)):
                return True
        return False

    def _check_window(self, sf, fn, window: list[ast.stmt],
                      close_line: int, var: str):
        dispatched = None
        fenced = False
        for stmt in window:
            for node in ast.walk(stmt):
                if not isinstance(node, ast.Call):
                    continue
                name = call_name(node)
                if name in ASYNC_DISPATCH and dispatched is None:
                    dispatched = (name, node.lineno)
                if name in FENCES:
                    fenced = True
        if dispatched is not None and not fenced:
            name, line = dispatched
            yield Finding(
                "BNG050", sf.path, close_line,
                f"timing window `{var}` (closed here) spans the async "
                f"dispatch `{name}` (line {line}) with no "
                f"block_until_ready/force — this measures enqueue cost, "
                f"not device time (the CPU-headline gray-failure class)",
                scope=f"{fn.name}", detail=f"{var}-{name}")
