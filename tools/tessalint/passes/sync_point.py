"""Rule ``sync`` — device→host transfers in device-resident modules.

The fused decide() path guarantees ONE device→host readout per round
(``BENCH_fused_decide.json``); the identity-keyed engine guarantees
readouts only at documented points (assignment extraction, the batched
match prologue, the LRU park).  Any other transfer is a silent sync that
shows up as a per-round latency cliff long before a benchmark catches it.

In modules the manifest declares device-resident, flag:

* ``np.asarray`` / ``np.array`` / ``np.ascontiguousarray`` on a value
  that (transitively) came from ``jax.numpy`` / ``jax.lax`` / another
  device producer;
* ``jax.device_get`` — ALWAYS flagged: every sanctioned readout is
  pragma-annotated, so the set of syncs is closed under review;
* ``.item()`` / ``.tolist()`` and ``float()/int()/bool()/complex()``
  coercions of device values;
* ``if`` / ``while`` tests and ``for`` iteration over device values
  (host control flow forces a blocking transfer);
* f-strings / ``print`` / ``repr`` / ``str`` formatting device values.

Taint is a per-scope, flow-insensitive fixpoint over assignments: a name
assigned from an expression containing a device producer (or a tainted
name) is tainted; host converters and ``jax.device_get`` LAUNDER their
result (the result is a host value — the call itself is what gets
flagged).  Parameters annotated ``jax.Array`` / ``jnp.ndarray`` are
tainted seeds, and nested functions inherit the enclosing scope's taint
(closure capture).  Flow-sensitive tracer tracking is the next rung on
the ladder (see tools/tessalint/README.md).

Options:
* ``device_producers``: extra canonical call prefixes that return device
  values (e.g. ``"repro.kernels."``).
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Set

from tools.tessalint.astutil import call_name
from tools.tessalint.findings import Finding
from tools.tessalint.passes.base import FileContext

RULE = "sync"

_PRODUCER_PREFIXES = (
    "jax.numpy.",
    "jax.lax.",
    "jax.nn.",
    "jax.random.",
    "jax.scipy.",
    "jax.experimental.",
)
_PRODUCER_CALLS = {"jax.vmap", "jax.pmap", "jax.shard_map", "jax.grad", "jax.value_and_grad"}
_HOST_CONVERTERS = {"numpy.asarray", "numpy.array", "numpy.ascontiguousarray"}
_ALWAYS_SYNC = {"jax.device_get"}
_COERCIONS = {"float", "int", "bool", "complex"}
_FORMATTERS = {"print", "repr", "str"}
_SYNC_METHODS = {"item", "tolist", "block_until_ready"}
# Array metadata that lives host-side: reading it never transfers data.
_META_ATTRS = {"shape", "ndim", "size", "dtype", "weak_type", "sharding", "nbytes", "itemsize"}

_FUNC = (ast.FunctionDef, ast.AsyncFunctionDef)


def own_nodes(scope_node: ast.AST) -> Iterator[ast.AST]:
    """Walk a scope's OWN nodes: stop at nested function boundaries (their
    bodies are separate scopes), but keep lambdas and comprehensions."""
    stack = list(ast.iter_child_nodes(scope_node))
    while stack:
        node = stack.pop()
        yield node
        if not isinstance(node, _FUNC):
            stack.extend(ast.iter_child_nodes(node))


def _param_is_device(arg: ast.arg) -> bool:
    if arg.annotation is None:
        return False
    text = ast.unparse(arg.annotation)
    return any(tag in text for tag in ("jax.Array", "jnp.ndarray", "jax.numpy.ndarray"))


class _Scope:
    def __init__(self, ctx: FileContext, node, inherited: Set[str]):
        self.ctx = ctx
        self.node = node
        self.taint: Set[str] = set(inherited)
        if isinstance(node, _FUNC):
            a = node.args
            for arg in [*a.posonlyargs, *a.args, *a.kwonlyargs]:
                if _param_is_device(arg):
                    self.taint.add(arg.arg)
        self.extra = tuple(ctx.options.get("device_producers", []))

    def device_expr(self, node: ast.AST) -> bool:
        """True when the expression reads DEVICE DATA.  Prunes subtrees
        that only touch host-side metadata or launder to host:

        * host converters / ``device_get`` calls — their result is a host
          value (the call itself is flagged separately);
        * ``.shape`` / ``.ndim`` / ``.size`` / ``.dtype`` — array
          metadata lives host-side, branching on it never transfers;
        * ``is`` / ``is not`` comparisons — object identity, no read.
        """
        if isinstance(node, ast.Call):
            q = call_name(node, self.ctx.imports)
            if q in _HOST_CONVERTERS or q in _ALWAYS_SYNC:
                return False
            if q is not None and (
                q.startswith(_PRODUCER_PREFIXES)
                or q in _PRODUCER_CALLS
                or any(q.startswith(p) for p in self.extra)
            ):
                return True
        elif isinstance(node, ast.Attribute):
            if node.attr in _META_ATTRS:
                return False
        elif isinstance(node, ast.Compare):
            if all(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
                return False
        elif isinstance(node, ast.Name):
            return node.id in self.taint
        return any(self.device_expr(c) for c in ast.iter_child_nodes(node))

    def _rhs_taints(self, value: ast.AST) -> bool:
        return self.device_expr(value)

    def _bind(self, target: ast.AST) -> List[str]:
        if isinstance(target, ast.Name):
            return [target.id]
        if isinstance(target, (ast.Tuple, ast.List)):
            out = []
            for el in target.elts:
                out.extend(self._bind(el))
            return out
        return []

    def compute_taint(self) -> None:
        for _ in range(4):  # fixpoint: chains of assignments
            before = len(self.taint)
            for stmt in own_nodes(self.node):
                if isinstance(stmt, ast.Assign):
                    if self._rhs_taints(stmt.value):
                        for t in stmt.targets:
                            self.taint.update(self._bind(t))
                elif isinstance(stmt, (ast.AugAssign, ast.AnnAssign)):
                    if stmt.value is not None and self._rhs_taints(stmt.value):
                        self.taint.update(self._bind(stmt.target))
            if len(self.taint) == before:
                break


def run(ctx: FileContext) -> List[Finding]:
    findings: List[Finding] = []

    def flag(node, message, hint, severity="P1"):
        findings.append(
            Finding(
                RULE,
                ctx.path,
                node.lineno,
                node.col_offset,
                message,
                snippet=ctx.snippet(node.lineno),
                hint=hint,
                severity=severity,
                end_line=getattr(node, "end_lineno", node.lineno) or node.lineno,
            )
        )

    def check_scope(scope_node: ast.AST, inherited: Set[str]) -> None:
        scope = _Scope(ctx, scope_node, inherited)
        scope.compute_taint()

        for node in own_nodes(scope_node):
            if isinstance(node, _FUNC):
                check_scope(node, scope.taint)
                continue
            if isinstance(node, ast.Call):
                q = call_name(node, ctx.imports)
                if q in _ALWAYS_SYNC:
                    flag(
                        node,
                        "jax.device_get is a device→host sync point",
                        "if this is THE sanctioned readout, annotate it: "
                        "# tessalint: sync-ok(<why this readout is in budget>)",
                    )
                elif q in _HOST_CONVERTERS and any(
                    scope.device_expr(a) for a in node.args
                ):
                    flag(
                        node,
                        f"{q.split('.')[-1]} on a device value forces a "
                        "device→host transfer",
                        "keep the value on device (jnp), or move the readout "
                        "to the round's single sanctioned sync",
                    )
                elif (
                    q in _COERCIONS
                    and len(node.args) == 1
                    and scope.device_expr(node.args[0])
                ):
                    flag(
                        node,
                        f"{q}() coercion of a device value blocks on a "
                        "device→host transfer",
                        "coerce after the sanctioned readout, or keep the "
                        "value in the jitted program",
                    )
                elif q in _FORMATTERS and any(
                    scope.device_expr(a) for a in node.args
                ):
                    flag(
                        node,
                        f"{q}() of a device value forces a device→host "
                        "transfer",
                        "log host-side copies from the sanctioned readout "
                        "instead",
                        severity="P2",
                    )
                elif (
                    isinstance(node.func, ast.Attribute)
                    and node.func.attr in _SYNC_METHODS
                    and scope.device_expr(node.func.value)
                ):
                    flag(
                        node,
                        f".{node.func.attr}() on a device value is a "
                        "device→host sync point",
                        "read the value out with the round's single "
                        "sanctioned sync instead",
                    )
            elif isinstance(node, (ast.If, ast.While)) and scope.device_expr(
                node.test
            ):
                flag(
                    node.test,
                    "host control flow on a device value forces a blocking "
                    "transfer",
                    "use jnp.where / lax.cond, or branch on the host copy "
                    "from the sanctioned readout",
                )
            elif isinstance(node, ast.For) and scope.device_expr(node.iter):
                flag(
                    node.iter,
                    "host iteration over a device value syncs per element",
                    "vectorise with jnp, or iterate the host copy from the "
                    "sanctioned readout",
                )
            elif isinstance(node, ast.FormattedValue) and scope.device_expr(
                node.value
            ):
                flag(
                    node,
                    "f-string formats a device value (forces a device→host "
                    "transfer)",
                    "format the host copy from the sanctioned readout",
                    severity="P2",
                )

    check_scope(ctx.tree, set())
    return findings
