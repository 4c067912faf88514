"""The rule catalog: eight AST rules holding the repo's code contracts.

Each rule documents the contract it holds, the allowlist (modules that
legitimately own the forbidden pattern), and the regex-era failure modes it
fixes where it replaces one of the old line-scanning guards.
"""
from __future__ import annotations

import ast

from repro.analysis.findings import Finding
from repro.analysis.lint import Rule, Source, ancestors, parent, register

# --------------------------------------------------------------------------
# no-string-dispatch
# --------------------------------------------------------------------------

_METHOD_ATTRS = {"method", "embedding_method"}


def _string_const(node: ast.AST) -> bool:
    return isinstance(node, ast.Constant) and isinstance(node.value, str)


def _string_collection(node: ast.AST) -> bool:
    return (isinstance(node, (ast.Tuple, ast.List, ast.Set))
            and node.elts
            and all(_string_const(e) for e in node.elts))


@register
class NoStringDispatch(Rule):
    """Method dispatch goes through the registry, not string compares.

    PR 3 replaced ``if spec.method == "alpt"`` chains with the
    ``EmbeddingMethod`` registry; this rule keeps them out everywhere but
    ``methods/`` (the registry layer itself).  AST-level wins over the old
    regex: comparisons inside strings/comments no longer false-positive,
    and ``match spec.method: case "lpt"`` no longer false-negatives.
    """

    name = "no-string-dispatch"
    hint = ("dispatch through the EmbeddingMethod registry "
            "(methods.get(spec.method)) or a capability flag on the method")
    exclude = ("src/repro/methods/*",)

    def check(self, source: Source) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(source.tree):
            if isinstance(node, ast.Compare):
                sides = [node.left, *node.comparators]
                if not any(self._is_method_attr(s) for s in sides):
                    continue
                for op, comp in zip(node.ops, node.comparators):
                    if isinstance(op, (ast.Eq, ast.NotEq)) and (
                            _string_const(comp) or _string_const(node.left)):
                        out.append(self.finding(
                            source, node, "string comparison against "
                            "`.method` dispatches on a name"))
                        break
                    if isinstance(op, (ast.In, ast.NotIn)) and (
                            _string_collection(comp) or _string_const(comp)):
                        out.append(self.finding(
                            source, node, "membership test of `.method` against "
                            "string literals dispatches on a name"))
                        break
            elif isinstance(node, ast.Match):
                if self._is_method_attr(node.subject) and any(
                        self._case_is_string(c) for c in node.cases):
                    out.append(self.finding(
                        source, node, "match over `.method` with string case "
                        "patterns dispatches on a name"))
            elif isinstance(node, ast.Call):
                f = node.func
                if (isinstance(f, ast.Attribute)
                        and f.attr in ("startswith", "endswith")
                        and self._is_method_attr(f.value)):
                    out.append(self.finding(
                        source, node, f"`.method.{f.attr}(...)` dispatches on a "
                        "name prefix"))
        return out

    @staticmethod
    def _is_method_attr(node: ast.AST) -> bool:
        return isinstance(node, ast.Attribute) and node.attr in _METHOD_ATTRS

    @staticmethod
    def _case_is_string(case: ast.match_case) -> bool:
        pat = case.pattern
        return (isinstance(pat, ast.MatchValue)
                and _string_const(pat.value))


# --------------------------------------------------------------------------
# no-raw-code-casts
# --------------------------------------------------------------------------

_CODE_DTYPES = {
    "jax.numpy.int8", "jax.numpy.uint8", "numpy.int8", "numpy.uint8",
}
_ARRAY_CTORS = {
    "jax.numpy.asarray", "jax.numpy.array", "numpy.asarray", "numpy.array",
}


def _is_code_dtype(source: Source, node: ast.AST | None) -> bool:
    if node is None:
        return False
    if isinstance(node, ast.Constant) and node.value in ("int8", "uint8"):
        return True
    return source.dotted(node) in _CODE_DTYPES


@register
class NoRawCodeCasts(Rule):
    """int8/uint8 casts happen only inside the quantization layers.

    A stray ``.astype(int8)`` outside ``core/quant.py`` /
    ``core/codestore.py`` / ``kernels/`` silently truncates without the
    SR/clip semantics of ``quant.quantize`` — the exact bug class ALPT's
    learned step sizes exist to prevent.  The AST version also catches the
    regex-era false negatives: ``jnp.asarray(x, dtype=jnp.int8)``,
    ``jnp.array(x, "int8")``, aliased imports, and
    ``lax.convert_element_type`` — and no longer fires on casts mentioned
    in strings or comments.
    """

    name = "no-raw-code-casts"
    hint = ("route through repro.core.quant (quantize/sr_round) or the "
            "CodeStore container; raw int8 casts skip SR/clip semantics")
    exclude = (
        "src/repro/core/codestore.py",
        "src/repro/core/quant.py",
        "src/repro/kernels/*",
    )

    def check(self, source: Source) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(source.tree):
            if not isinstance(node, ast.Call):
                continue
            bad = self._cast_dtype_node(source, node)
            if bad is not None:
                out.append(self.finding(
                    source, node, "raw cast of codes to "
                    f"{ast.unparse(bad)} outside the quantization layers"))
        return out

    def _cast_dtype_node(self, source: Source,
                         call: ast.Call) -> ast.AST | None:
        """The dtype argument node when ``call`` is a raw int8/uint8 cast."""
        f = call.func
        kw = {k.arg: k.value for k in call.keywords}
        # x.astype(int8) / x.astype(dtype=int8)
        if isinstance(f, ast.Attribute) and f.attr == "astype":
            cand = call.args[0] if call.args else kw.get("dtype")
            if _is_code_dtype(source, cand):
                return cand
            return None
        dotted = source.dotted(f)
        # jnp.asarray(x, jnp.int8) / jnp.array(x, dtype="int8")
        if dotted in _ARRAY_CTORS:
            cand = call.args[1] if len(call.args) > 1 else kw.get("dtype")
            if _is_code_dtype(source, cand):
                return cand
            return None
        # lax.convert_element_type(x, jnp.int8)
        if dotted == "jax.lax.convert_element_type":
            cand = call.args[1] if len(call.args) > 1 else kw.get("new_dtype")
            if _is_code_dtype(source, cand):
                return cand
            return None
        # x.view(jnp.int8): a reinterpret-cast is as raw as a value cast.
        if isinstance(f, ast.Attribute) and f.attr == "view":
            cand = call.args[0] if call.args else kw.get("dtype")
            if _is_code_dtype(source, cand):
                return cand
            return None
        # jax.random.randint(key, shape, lo, hi, jnp.int8): minting codes
        # without quantization semantics.  Synthetic-code benchmark setups
        # that want exactly this carry a reviewed suppression entry.
        if dotted == "jax.random.randint":
            cand = call.args[4] if len(call.args) > 4 else kw.get("dtype")
            if _is_code_dtype(source, cand):
                return cand
        return None


# --------------------------------------------------------------------------
# no-direct-storage-access
# --------------------------------------------------------------------------

_SEAM_METHODS = {"unpack", "take", "set_rows", "where_rows"}
_PACK_FUNCS = {"pack_codes", "unpack_codes"}


@register
class NoDirectStorageAccess(Rule):
    """Row access goes through the ``repro.storage.base`` seam helpers.

    Outside the storage layers, calling the :class:`RowStore` protocol
    methods directly (``container.take(ids)``, ``container.unpack()``) —
    or the byte-level ``pack_codes``/``unpack_codes`` — couples the call
    site to one container layout and skips the raw-array dispatch the
    module-level helpers (``take_rows``/``set_rows``/``where_rows``/
    ``logical_codes``) provide.  PR 7's tiered cache only slotted in with
    zero trainer edits because every access already ran through the seam;
    this rule keeps it that way.
    """

    name = "no-direct-storage-access"
    hint = ("use repro.storage.base helpers (take_rows/set_rows/where_rows/"
            "logical_codes) — they dispatch over every container layout")
    exclude = (
        "src/repro/core/codestore.py",
        "src/repro/core/quant.py",
        "src/repro/storage/*",
        "src/repro/kernels/*",
    )
    # byte-level (un)packing additionally belongs to the sync wire format
    _pack_exclude = ("src/repro/dist/collectives.py",)

    def check(self, source: Source) -> list[Finding]:
        import fnmatch as _fn
        out: list[Finding] = []
        pack_ok = any(_fn.fnmatch(source.rel, g) for g in self._pack_exclude)
        for node in ast.walk(source.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if isinstance(f, ast.Attribute) and f.attr in _SEAM_METHODS:
                recv = f.value
                # Any import-bound receiver is a module (rowstore.set_rows,
                # jnp.take) — containers are always locals/attributes.
                if isinstance(recv, ast.Name) and (
                        recv.id in source.aliases
                        or recv.id in ("self", "cls")):
                    continue
                # struct.unpack etc.: only flag zero/low-arity protocol
                # shapes — unpack() takes none, take(ids) exactly one.
                if f.attr == "unpack" and (node.args or node.keywords):
                    continue
                if f.attr == "take" and (len(node.args) != 1
                                         or node.keywords):
                    continue
                out.append(self.finding(
                    source, node, f"direct RowStore method `.{f.attr}(...)` "
                    "outside the storage layers"))
            else:
                dotted = source.dotted(f) or ""
                tail = dotted.rsplit(".", 1)[-1]
                if tail in _PACK_FUNCS and not pack_ok:
                    out.append(self.finding(
                        source, node, f"byte-level `{tail}` outside the storage "
                        "layers/sync wire"))
        return out


# --------------------------------------------------------------------------
# rng-key-discipline
# --------------------------------------------------------------------------

_KEY_PRODUCERS = {
    "jax.random.PRNGKey", "jax.random.key", "jax.random.split",
    "jax.random.fold_in", "jax.random.wrap_key_data", "jax.random.clone",
}
# Calls that read a key without consuming its entropy (fold_in *derives*;
# iter/next drive the split-iterator idiom).
_NONCONSUMING = {
    "jax.random.fold_in", "jax.random.key_data", "jax.random.clone",
    "iter", "next", "len", "print", "repr", "str", "id", "hash", "type",
    "isinstance", "list", "tuple",
}
_KEY_PARAM_SUFFIXES = ("key", "rng", "keys", "rngs")


def _is_key_param(name: str) -> bool:
    return name in ("key", "rng") or name.endswith(_KEY_PARAM_SUFFIXES)


@register
class RngKeyDiscipline(Rule):
    """A PRNGKey/split result is consumed at most once per scope.

    Reusing a key feeds *correlated* noise into two draws — for SR
    quantization that couples rounding noise across tensors and silently
    biases the very estimator LPT/ALPT's convergence argument (paper §3)
    rests on.  The sanctioned patterns stay legal: ``fold_in`` derivation,
    ``key, sub = split(key)`` reassignment, split-iterator ``next(keys)``,
    and per-branch single use.

    Abstract interpretation, one scope at a time: each tracked key has a
    consumption count; loop bodies are walked twice (a use per iteration
    without in-loop rederivation counts as reuse); ``if``/``try`` branches
    merge by max.  Nested ``def``/``lambda`` bodies are separate scopes.
    """

    name = "rng-key-discipline"
    hint = ("derive per-use subkeys: `key, sub = jax.random.split(key)` or "
            "`jax.random.fold_in(key, tag)` — never reuse a consumed key")
    # the vendored hypothesis stub threads a *stateful* stdlib Random named
    # `rng`; jax key discipline does not apply to it
    exclude = ("src/repro/_compat/*",)

    def check(self, source: Source) -> list[Finding]:
        out: list[Finding] = []
        scopes: list[tuple[str, list[ast.stmt], list[str]]] = [
            ("<module>", source.tree.body, [])
        ]
        for node in ast.walk(source.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                params = [
                    a.arg for a in (node.args.posonlyargs + node.args.args
                                    + node.args.kwonlyargs)
                    if _is_key_param(a.arg)
                ]
                scopes.append((node.name, node.body, params))
        for scope_name, body, key_params in scopes:
            walker = _KeyScopeWalker(source, self, scope_name)
            for p in key_params:
                walker.env[(p, None)] = 0
            walker.walk_block(body)
            out.extend(walker.findings)
        return out


class _KeyScopeWalker:
    """Linear consumption counting over one scope's statement list."""

    def __init__(self, source: Source, rule: Rule, scope: str):
        self.source = source
        self.rule = rule
        self.scope = scope
        self.env: dict[tuple[str, int | None], int] = {}
        self.findings: list[Finding] = []
        self.flagged: set[tuple[str, int | None]] = set()

    # ---- statements ----

    def walk_block(self, stmts: list[ast.stmt]) -> None:
        for st in stmts:
            self.walk_stmt(st)

    def walk_stmt(self, st: ast.stmt) -> None:
        if isinstance(st, (ast.FunctionDef, ast.AsyncFunctionDef,
                           ast.ClassDef)):
            return  # separate scope
        if isinstance(st, (ast.Assign, ast.AnnAssign, ast.AugAssign)):
            value = st.value
            if value is not None:
                self.visit_expr(value)
            targets = (st.targets if isinstance(st, ast.Assign)
                       else [st.target])
            for t in targets:
                self._bind(t, value)
            return
        if isinstance(st, ast.If):
            self.visit_expr(st.test)
            self._branches([st.body, st.orelse])
            return
        if isinstance(st, (ast.For, ast.AsyncFor)):
            self.visit_expr(st.iter)
            self._bind(st.target, None)
            # two passes ~ two iterations: a use per iteration without
            # rederivation inside the body shows up as a double count.
            self.walk_block(st.body)
            self.walk_block(st.body)
            self.walk_block(st.orelse)
            return
        if isinstance(st, ast.While):
            self.visit_expr(st.test)
            self.walk_block(st.body)
            self.walk_block(st.body)
            self.walk_block(st.orelse)
            return
        if isinstance(st, (ast.With, ast.AsyncWith)):
            for item in st.items:
                self.visit_expr(item.context_expr)
                if item.optional_vars is not None:
                    self._bind(item.optional_vars, None)
            self.walk_block(st.body)
            return
        if isinstance(st, ast.Try):
            self._branches(
                [st.body] + [h.body for h in st.handlers] + [st.orelse])
            self.walk_block(st.finalbody)
            return
        for child in ast.iter_child_nodes(st):
            if isinstance(child, ast.expr):
                self.visit_expr(child)

    def _branches(self, blocks: list[list[ast.stmt]]) -> None:
        base = dict(self.env)
        merged = dict(self.env)
        for block in blocks:
            self.env = dict(base)
            self.walk_block(block)
            if any(isinstance(s, (ast.Return, ast.Raise, ast.Continue,
                                  ast.Break)) for s in block):
                continue  # terminated branch: never merges into fall-through
            for k, v in self.env.items():
                merged[k] = max(merged.get(k, 0), v) if k in base else v
            for k in set(base) - set(self.env):
                merged.pop(k, None)
        self.env = merged

    # ---- bindings ----

    def _bind(self, target: ast.AST, value: ast.expr | None) -> None:
        fresh = value is not None and self._produces_key(value)
        if isinstance(target, ast.Name):
            self._rebind(target.id, fresh)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for el in target.elts:
                if isinstance(el, ast.Name):
                    self._rebind(el.id, fresh)
                elif isinstance(el, ast.Starred) and isinstance(
                        el.value, ast.Name):
                    self._rebind(el.value.id, fresh)

    def _rebind(self, name: str, fresh: bool) -> None:
        for k in [k for k in self.env if k[0] == name]:
            del self.env[k]
        self.flagged = {f for f in self.flagged if f[0] != name}
        if fresh:
            self.env[(name, None)] = 0

    def _produces_key(self, value: ast.expr) -> bool:
        if isinstance(value, ast.Subscript):
            return self._produces_key(value.value)
        if not isinstance(value, ast.Call):
            return False
        dotted = self.source.dotted(value.func)
        if dotted in _KEY_PRODUCERS:
            return True
        tail = (dotted or "").rsplit(".", 1)[-1]
        if tail in ("iter",) and value.args:
            return self._produces_key(value.args[0])
        return tail in ("PRNGKey", "split", "fold_in")

    # ---- uses ----

    def visit_expr(self, expr: ast.expr) -> None:
        for node in ast.walk(expr):
            if isinstance(node, (ast.Lambda, ast.ListComp, ast.SetComp,
                                 ast.DictComp, ast.GeneratorExp)):
                continue  # their bodies are handled below / skipped
            if isinstance(node, ast.Call):
                self._visit_call(node)
        # comprehensions: walk the element twice (loop semantics)
        for node in ast.walk(expr):
            if isinstance(node, (ast.ListComp, ast.SetComp,
                                 ast.GeneratorExp)):
                for sub in ast.walk(node.elt):
                    if isinstance(sub, ast.Call):
                        self._visit_call(sub)

    def _visit_call(self, call: ast.Call) -> None:
        dotted = self.source.dotted(call.func) or ""
        tail = dotted.rsplit(".", 1)[-1]
        if dotted in _NONCONSUMING or tail in ("fold_in", "iter", "next"):
            return
        in_lambda = any(isinstance(a, ast.Lambda) for a in ancestors(call))
        if in_lambda:
            return  # deferred bodies are not linear uses of this scope
        args = list(call.args) + [k.value for k in call.keywords]
        for a in args:
            ref = self._key_ref(a)
            if ref is not None:
                self._consume(ref, call)

    def _key_ref(self, node: ast.expr) -> tuple[str, int | None] | None:
        if isinstance(node, ast.Name) and (node.id, None) in self.env:
            return (node.id, None)
        if (isinstance(node, ast.Subscript)
                and isinstance(node.value, ast.Name)):
            name = node.value.id
            if (name, None) in self.env or any(
                    k[0] == name for k in self.env):
                idx = node.slice
                if isinstance(idx, ast.Constant) and isinstance(
                        idx.value, int):
                    if (name, None) in self.env:
                        # promote the array to per-index tracking
                        del self.env[(name, None)]
                    self.env.setdefault((name, idx.value), 0)
                    return (name, idx.value)
                return None  # dynamic index: cannot reason, do not count
        return None

    def _consume(self, ref: tuple[str, int | None], at: ast.Call) -> None:
        self.env[ref] = self.env.get(ref, 0) + 1
        if self.env[ref] >= 2 and ref not in self.flagged:
            self.flagged.add(ref)
            name = ref[0] if ref[1] is None else f"{ref[0]}[{ref[1]}]"
            self.findings.append(self.rule.finding(
                self.source, at,
                f"PRNG key `{name}` consumed more than once in scope "
                f"`{self.scope}` — draws are correlated"))


# --------------------------------------------------------------------------
# no-silent-fallback
# --------------------------------------------------------------------------

_NOTE_NAMES = {"_note_fallback", "note_fallback"}


@register
class NoSilentFallback(Rule):
    """Every branch that leaves the Pallas path ticks the fallback counter.

    PR 4's contract: "fallbacks counted and never silent".  A wrapper
    returning a ``_ref_*`` jnp reference path without a ``_note_fallback``
    call hides a perf cliff — benchmarks would report kernels-on numbers
    while silently running the reference.  The explicit ``use_kernel=False``
    gate is *not* a fallback (the caller asked for the reference) and is
    exempt when the return sits under a ``use_kernel`` test.
    """

    name = "no-silent-fallback"
    hint = ("call _note_fallback(name, shape, reason) before returning the "
            "_ref_* path (or gate the branch on the explicit use_kernel "
            "switch)")
    include = ("src/repro/kernels/*",)

    def check(self, source: Source) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(source.tree):
            if not isinstance(node, ast.Return) or node.value is None:
                continue
            ref = self._ref_call(node.value)
            if ref is None:
                continue
            fn = next((a for a in ancestors(node) if isinstance(
                a, (ast.FunctionDef, ast.AsyncFunctionDef))), None)
            if fn is None or fn.name.startswith(("_ref_", "ref_")):
                continue  # reference impls compose freely
            if self._under_use_kernel_gate(node):
                continue
            if self._noted_before(node, fn):
                continue
            out.append(self.finding(
                source, node, f"silent fallback: `{fn.name}` returns `{ref}` "
                "without ticking the fallback counter"))
        return out

    @staticmethod
    def _ref_call(expr: ast.expr) -> str | None:
        for sub in ast.walk(expr):
            if isinstance(sub, ast.Call):
                f = sub.func
                name = (f.id if isinstance(f, ast.Name)
                        else f.attr if isinstance(f, ast.Attribute) else "")
                if name.startswith(("_ref_", "ref_")):
                    return name
        return None

    @staticmethod
    def _under_use_kernel_gate(node: ast.AST) -> bool:
        for anc in ancestors(node):
            if isinstance(anc, ast.If):
                for sub in ast.walk(anc.test):
                    ident = (sub.id if isinstance(sub, ast.Name)
                             else sub.attr if isinstance(sub, ast.Attribute)
                             else "")
                    if "use_kernel" in ident:
                        return True
        return False

    @staticmethod
    def _noted_before(node: ast.Return,
                      fn: ast.FunctionDef | ast.AsyncFunctionDef) -> bool:
        """A note call in any statement lexically preceding the return on
        its ancestor path (same block or an enclosing one)."""
        path = {node} | set(ancestors(node))
        blocks: list[list[ast.stmt]] = [fn.body]
        for anc in ancestors(node):
            for field in ("body", "orelse", "finalbody"):
                block = getattr(anc, field, None)
                if isinstance(block, list) and any(
                        s in path for s in block):
                    blocks.append(block)
        for block in blocks:
            for st in block:
                if st in path:
                    break
                for sub in ast.walk(st):
                    if isinstance(sub, ast.Call):
                        f = sub.func
                        name = (f.id if isinstance(f, ast.Name)
                                else f.attr
                                if isinstance(f, ast.Attribute) else "")
                        if name in _NOTE_NAMES:
                            return True
        return False


# --------------------------------------------------------------------------
# no-unfenced-model-grad
# --------------------------------------------------------------------------

_GRAD_FUNCS = {"jax.grad", "jax.value_and_grad"}
# The dense formulation materializes the fake-quant table as a plain jit
# input (no storage graph to pin), so its delta-grad backward needs no
# fence; the function name marks the formulation.
_FENCE_EXEMPT_FUNCTIONS = {"dense_delta_grad"}


@register
class NoUnfencedModelGrad(Rule):
    """Fused-path model backwards compile behind ``fence.fence_call``.

    PR 7's cache-parity bar (cache-on bitwise == cache-off) holds because
    the model backward in every fused step compiles inside the
    ``core/fence.py`` opaque-trip-count loop — XLA cannot re-associate it
    against whatever storage graph surrounds it.  A direct
    ``jax.grad(f)(x)`` in a fused path reopens that seam.  Legal shapes:
    passing the grad callable *to* ``fence_call`` (unfenced construction,
    fenced invocation) and the dense formulation (``dense_delta_grad``).
    """

    name = "no-unfenced-model-grad"
    hint = ("wrap the call: fence.fence_call(jax.value_and_grad(f), args, "
            "tick=...) — see core/fence.py")
    include = ("src/repro/methods/*", "src/repro/core/*")
    exclude = ("src/repro/core/fence.py",)

    def check(self, source: Source) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(source.tree):
            if not isinstance(node, ast.Call):
                continue
            if source.dotted(node.func) not in _GRAD_FUNCS:
                continue
            par = parent(node)
            invoked = isinstance(par, ast.Call) and par.func is node
            if not invoked:
                continue  # constructed, not invoked (e.g. fence_call arg)
            fn = next((a for a in ancestors(node) if isinstance(
                a, (ast.FunctionDef, ast.AsyncFunctionDef))), None)
            if fn is not None and fn.name in _FENCE_EXEMPT_FUNCTIONS:
                continue
            out.append(self.finding(
                source, node, "model backward invoked outside fence_call in a "
                "fused path"))
        return out


# --------------------------------------------------------------------------
# no-silent-except


_BROAD_EXC = {"Exception", "BaseException"}
_LOG_ATTRS = {
    "debug", "info", "warning", "warn", "error", "exception", "critical",
    "log",
}
#: Calls that count as "ticking a counter": collection mutations the failure
#: accounting paths use (e.g. CheckpointManager.corrupt_steps.append).
_COUNTER_ATTRS = {"append", "add", "update", "merge"}


@register
class NoSilentExcept(Rule):
    """Broad exception handlers must re-raise, log, or tick a counter.

    The fault-injection harness (repro.faults) only proves recovery works
    if failures are *visible*: a bare ``except:`` or ``except Exception:
    pass`` swallows an injected fault and the chaos suite reads it as a
    pass.  Narrow handlers (``except CorruptCheckpointError:``) stay legal —
    catching a specific failure is a decision; catching everything silently
    is a hole.  AST-level wins over a regex guard: ``except`` mentioned in
    docstrings/comments never fires, and a handler that logs three
    statements down is recognized.
    """

    name = "no-silent-except"
    hint = ("re-raise, log through a logger/print, or tick a failure "
            "counter — a silently swallowed broad except hides faults "
            "from the recovery layer")

    def check(self, source: Source) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(source.tree):
            if not isinstance(node, ast.ExceptHandler):
                continue
            if not self._broad(node.type):
                continue
            if self._handled(node):
                continue
            what = ("bare `except:`" if node.type is None
                    else f"`except {ast.unparse(node.type)}:`")
            out.append(self.finding(
                source, node,
                f"{what} swallows the error without re-raise, log, or "
                "counter"))
        return out

    @staticmethod
    def _broad(t: ast.AST | None) -> bool:
        if t is None:
            return True
        names = t.elts if isinstance(t, ast.Tuple) else [t]
        for n in names:
            if isinstance(n, ast.Name) and n.id in _BROAD_EXC:
                return True
            if isinstance(n, ast.Attribute) and n.attr in _BROAD_EXC:
                return True
        return False

    @staticmethod
    def _handled(handler: ast.ExceptHandler) -> bool:
        for stmt in handler.body:
            for sub in ast.walk(stmt):
                if isinstance(sub, ast.Raise):
                    return True
                if isinstance(sub, ast.AugAssign):
                    return True  # counter tick: `self.failures += 1`
                if isinstance(sub, ast.Call):
                    f = sub.func
                    if isinstance(f, ast.Name) and f.id == "print":
                        return True
                    if isinstance(f, ast.Attribute) and f.attr in (
                            _LOG_ATTRS | _COUNTER_ATTRS):
                        return True
        return False


# --------------------------------------------------------------------------
# no-host-sync
# --------------------------------------------------------------------------

_JIT_WRAPPERS = {
    "jax.jit", "jax.pmap", "shard_map", "jax.experimental.shard_map.shard_map",
}
_HOST_PULL_FUNCS = {"numpy.asarray", "numpy.array", "jax.device_get"}


@register
class NoHostSync(Rule):
    """Library code never forces a device sync; step functions never pull
    values to the host.

    The obs contract (PR 10) keeps dispatch fully async when tracing is
    off: the sanctioned ``block_until_ready`` calls in ``src/repro`` are the
    tracer's span-edge fence (it runs only while tracing is armed, at host
    span boundaries) and ``training/run_ahead.py``'s wait for the previous
    step, each a reviewed ``analysis-suppressions.txt`` entry.
    Anywhere else a ``.block_until_ready()`` stalls the pipeline for every
    caller, traced or not.

    Inside *jit scopes* — functions decorated with / passed to
    ``jax.jit``/``pmap``/``shard_map``, and anything lexically nested in
    one — ``np.asarray``/``np.array``/``jax.device_get`` additionally
    force a device->host transfer at trace time (a hidden sync and a
    constant-folded copy baked into the compiled program).  Host-side
    policy code may convert freely; traced step functions may not.
    Benchmarks are excluded: min-of-N timing *requires* explicit syncs.
    """

    name = "no-host-sync"
    hint = ("keep device values on device: drop the block_until_ready (the "
            "obs tracer fences span edges when armed), and inside jitted "
            "step functions use jnp.* — np.asarray/device_get force a "
            "device->host pull at trace time")
    exclude = ("benchmarks/*",)

    def check(self, source: Source) -> list[Finding]:
        out: list[Finding] = []
        for node in ast.walk(source.tree):
            if not isinstance(node, ast.Call):
                continue
            f = node.func
            if ((isinstance(f, ast.Attribute)
                 and f.attr == "block_until_ready")
                    or source.dotted(f) == "jax.block_until_ready"):
                out.append(self.finding(
                    source, node, "host sync: `block_until_ready` outside "
                    "the tracer's reviewed span-edge fence"))
        scopes = {id(fn): fn for fn in self._jit_scopes(source)}
        for node in ast.walk(source.tree):
            if not isinstance(node, ast.Call):
                continue
            dotted = source.dotted(node.func)
            if dotted not in _HOST_PULL_FUNCS:
                continue
            # attribute to the innermost enclosing function only
            fn = next((a for a in ancestors(node) if isinstance(
                a, (ast.FunctionDef, ast.AsyncFunctionDef))), None)
            if fn is not None and id(fn) in scopes:
                out.append(self.finding(
                    source, node, f"`{dotted}` inside jit scope "
                    f"`{fn.name}` pulls a device value to the host at "
                    "trace time"))
        return out

    def _jit_scopes(self, source: Source):
        """FunctionDefs compiled by jax: jit-decorated, passed to a jit
        wrapper by name, or lexically nested in either."""
        defs: dict[str, list[ast.AST]] = {}
        roots: list[ast.AST] = []
        for node in ast.walk(source.tree):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                defs.setdefault(node.name, []).append(node)
                if any(self._is_jit_expr(source, d)
                       for d in node.decorator_list):
                    roots.append(node)
        for node in ast.walk(source.tree):
            if (isinstance(node, ast.Call)
                    and source.dotted(node.func) in _JIT_WRAPPERS
                    and node.args and isinstance(node.args[0], ast.Name)):
                roots.extend(defs.get(node.args[0].id, []))
        seen: set[int] = set()
        for root in roots:
            for sub in ast.walk(root):
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef)
                              ) and id(sub) not in seen:
                    seen.add(id(sub))
                    yield sub

    @staticmethod
    def _is_jit_expr(source: Source, node: ast.AST) -> bool:
        """`@jax.jit`, `@partial(jax.jit, ...)`, `@jax.jit(...)` shapes."""
        if source.dotted(node) in _JIT_WRAPPERS:
            return True
        if isinstance(node, ast.Call):
            if source.dotted(node.func) in _JIT_WRAPPERS:
                return True
            if (source.dotted(node.func) or "").endswith("partial"):
                return any(source.dotted(a) in _JIT_WRAPPERS
                           for a in node.args)
        return False
