"""Zero-copy lifetime dataflow rules: the interlock static half.

PR 9/12 bought their speed by replacing copies with MEMORYVIEWS over
buffers that outlive or get *recycled* under them — offload staging
pages are reused warm across batches, bufferlist fragments alias
caller arrays, and `Frame.read` segments window the receive body. A
frame's body itself is never recycled: msg/transport.py allocates it
once, at the body's length, has the kernel fill it, and it lives as
long as any segment view does. What IS reused on the receive side is
each connection's small spill buffer, and nothing leaves that but
`bytes()` copies; the `segments` rule stays, so that a body pool
could never be introduced silently. The send side holds views too:
`Frame.encode_parts` hands a large frame's segments to the transport
by reference, and its queue keeps a view of each until the kernel has
the last byte, across any number of awaits. That is safe for the same
reason and no other: what becomes `Message.data` is `bytes`, a view of
an rx body, or an array nobody writes again, never a window onto a
recycled source — which is what `view-escape` keeps off object
attributes, `msg.data` among them. The discipline was hand-audited;
these rules make the audit mechanical, the way `loop-affinity` froze
the loop-handle discipline:

  * `view-escape` — a view derived from a pooled/recycled source
    (staging pages via `get_staging`, frame `segments`, raw
    `memoryview(...)` windows) must not be STORED on an object/
    container or RETURNED without materialization: once it outlives
    its dispatch scope, nothing ties its lifetime to the buffer's
    recycle point, and the first reuse rewrites bytes under it.
  * `view-across-await` — holding a RECYCLED-source view (staging
    pages, frame segments) across an `await`: the suspension is
    exactly where another task can recycle the buffer, so the resumed
    code reads the next batch's bytes. Materialize before suspending,
    or re-derive the view after.

Both are local-dataflow rules (per function scope, no
cross-function propagation) tuned for precision: a finding means the
pattern is textually present, not merely possible. Designed-in
zero-copy contracts (e.g. `Frame._parse_segments` returning views the
caller refcounts) carry justified `# radoslint: disable=` comments.
"""
from __future__ import annotations

import ast

from ceph_tpu.tools.radoslint.checkers import (_FUNCS, dotted,
                                               terminal_name)
from ceph_tpu.tools.radoslint.core import Finding, SourceFile, rule

#: call attrs that hand out a window onto a RECYCLED pool (the staging
#: slot API); results must never escape the dispatch scope
_POOLED_CALL_ATTRS = {"get_staging"}
#: attribute names whose subscripts/iteration yield receive-buffer
#: views (frame segments over the rx body)
_SEGMENT_ATTRS = {"segments"}
#: wrapping a view in any of these materializes (or intentionally
#: re-owns) the bytes — the escape hatch the rules push toward
_MATERIALIZERS = {"bytes", "bytearray", "tobytes", "copy", "deepcopy",
                  "array", "asarray", "concatenate", "frombuffer",
                  "list", "hexlify", "join", "guard_view"}


def _is_materialized(node: ast.AST) -> bool:
    """True when `node` wraps its operand in a copying constructor
    (`bytes(v)`, `np.array(v)`, `v.tobytes()`) — or the sanitizer's
    generation guard, which re-ties the view to the recycle point."""
    if isinstance(node, ast.Call):
        return terminal_name(node.func) in _MATERIALIZERS
    return False


def _source_label(node: ast.AST) -> str | None:
    """Classify an expression as a pooled-view producer.

    Returns "staging" (recycled pool), "frame-seg" (receive-buffer
    window), "view" (raw memoryview window), or None. Recycled sources
    ("staging"/"frame-seg") additionally feed `view-across-await`.
    """
    if isinstance(node, ast.Call):
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in _POOLED_CALL_ATTRS:
            return "staging"
        if isinstance(fn, ast.Name) and fn.id == "memoryview":
            return "view"
        return None
    if isinstance(node, ast.Subscript):
        if terminal_name(node.value) in _SEGMENT_ATTRS:
            return "frame-seg"
        # a slice of a producer is a window over the same pool
        return _source_label(node.value)
    return None


_RECYCLED = {"staging", "frame-seg"}


def _iter_functions(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


class _EventVisitor(ast.NodeVisitor):
    """Linearize one function body into a source-order event stream:

      ("bind", name, label, lineno)   tracked-view binding
      ("unbind", name)                name rebound to something clean
      ("use", name, lineno)           Load of a tracked-candidate name
      ("await", lineno)               suspension point

    An Await's OPERAND is visited before the await event is emitted, so
    `await f(view)` orders the use before the suspension (handing a
    view INTO an awaited call is fine; resuming with it is not).
    Nested function bodies are skipped — their views live a different
    lifetime."""

    def __init__(self):
        self.events: list[tuple] = []

    def run(self, fn: ast.AST) -> list[tuple]:
        for stmt in fn.body:
            self.visit(stmt)
        return self.events

    def visit_FunctionDef(self, node):          # skip nested scopes
        pass

    visit_AsyncFunctionDef = visit_FunctionDef
    visit_Lambda = visit_FunctionDef

    def visit_Await(self, node: ast.Await):
        self.generic_visit(node)
        self.events.append(("await", node.lineno))

    def visit_Assign(self, node: ast.Assign):
        self.visit(node.value)                  # uses in the RHS first
        if len(node.targets) == 1 and isinstance(node.targets[0], ast.Name):
            name = node.targets[0].id
            lbl = _source_label(node.value)
            if lbl is None and isinstance(node.value, ast.Subscript) and \
                    isinstance(node.value.value, ast.Name):
                # slice of a (possibly tracked) name: resolved later
                self.events.append(("bind-slice", name,
                                    node.value.value.id, node.lineno))
                return
            if lbl is not None and not _is_materialized(node.value):
                self.events.append(("bind", name, lbl, node.lineno))
            else:
                self.events.append(("unbind", name))
        else:
            for t in node.targets:
                self.visit(t)

    def visit_For(self, node: ast.For):
        self.visit(node.iter)
        if isinstance(node.target, ast.Name) and \
                terminal_name(node.iter) in _SEGMENT_ATTRS:
            self.events.append(("bind", node.target.id, "frame-seg",
                                node.lineno))
        for stmt in node.body + node.orelse:
            self.visit(stmt)

    def visit_Name(self, node: ast.Name):
        if isinstance(node.ctx, ast.Load):
            self.events.append(("use", node.id, node.lineno))


@rule("view-across-await", "file",
      "a view over a RECYCLED buffer (staging page, frame segment) "
      "used after an `await` that follows its derivation: the "
      "suspension point is exactly where another task can complete a "
      "batch and recycle the source, so the resumed code reads the "
      "next batch's bytes. Materialize before suspending, finish with "
      "the view first, or re-derive it after the await.")
def check_view_across_await(sf: SourceFile) -> list[Finding]:
    out: list[Finding] = []
    for fn in _iter_functions(sf.tree):
        if not isinstance(fn, ast.AsyncFunctionDef):
            continue
        events = _EventVisitor().run(fn)
        bound: dict[str, tuple[str, int, int]] = {}  # name->(lbl,pos,line)
        flagged: set[str] = set()
        awaits: list[int] = []
        for pos, ev in enumerate(events):
            kind = ev[0]
            if kind == "await":
                awaits.append(pos)
            elif kind == "bind":
                _, name, lbl, line = ev
                if lbl in _RECYCLED:
                    bound[name] = (lbl, pos, line)
                else:
                    bound.pop(name, None)
            elif kind == "bind-slice":
                _, name, src, line = ev
                ent = bound.get(src)
                if ent is not None:
                    bound[name] = (ent[0], pos, line)
                else:
                    bound.pop(name, None)
            elif kind == "unbind":
                bound.pop(ev[1], None)
            elif kind == "use":
                _, name, line = ev
                ent = bound.get(name)
                if ent is None or name in flagged:
                    continue
                lbl, bpos, bline = ent
                if any(bpos < a < pos for a in awaits):
                    flagged.add(name)
                    out.append(Finding(
                        sf.path, line, "view-across-await",
                        f"{lbl} view {name!r} (derived at line {bline}) "
                        f"used after an await: the source buffer can be "
                        f"recycled while this coroutine is suspended — "
                        f"materialize before the await or re-derive the "
                        f"view after it"))
    return out


# -- rule: view-escape --------------------------------------------------------

def _stmt_walk(stmts):
    """Source-order walk over every node of a statement list, skipping
    nested function bodies."""
    stack = list(reversed(list(stmts)))
    while stack:
        n = stack.pop()
        yield n
        if not isinstance(n, _FUNCS):
            stack.extend(reversed(list(ast.iter_child_nodes(n))))


def _value_label(node: ast.AST, tracked: dict) -> str | None:
    """Label of an expression: a producer, a tracked name, or a slice
    of a tracked name (still a window over the same pool)."""
    lbl = _source_label(node)
    if lbl is not None:
        return lbl
    if isinstance(node, ast.Name):
        ent = tracked.get(node.id)
        return ent if isinstance(ent, str) else None
    if isinstance(node, ast.Subscript) and isinstance(node.value, ast.Name):
        ent = tracked.get(node.value.id)
        return ent if isinstance(ent, str) else None
    return None


@rule("view-escape", "file",
      "a memoryview over a pooled/recycled buffer (offload staging "
      "pages via get_staging, frame `segments` windows, raw "
      "memoryview(...) slices) stored on an object attribute, appended "
      "to a container reachable through an attribute, or returned from "
      "the deriving scope. Nothing ties the escaped view's lifetime to "
      "the buffer's recycle point: the next batch/frame rewrites the "
      "bytes under it and the corruption surfaces stripes later. "
      "Materialize (`bytes(v)`, `.tobytes()`) before storing, or keep "
      "the view inside its dispatch scope. Designed-in zero-copy "
      "returns (refcounted fresh buffers) carry a justified "
      "`# radoslint: disable=view-escape`.")
def check_view_escape(sf: SourceFile) -> list[Finding]:
    out: list[Finding] = []
    for fn in _iter_functions(sf.tree):
        tracked: dict[str, str] = {}          # name -> label
        for node in _stmt_walk(fn.body):
            if isinstance(node, ast.Assign) and len(node.targets) == 1:
                tgt, val = node.targets[0], node.value
                lbl = _value_label(val, tracked)
                if isinstance(tgt, ast.Name):
                    if lbl is not None and not _is_materialized(val):
                        tracked[tgt.id] = lbl
                    else:
                        tracked.pop(tgt.id, None)     # rebound clean
                elif lbl is not None and not _is_materialized(val) and (
                        isinstance(tgt, ast.Attribute) or
                        (isinstance(tgt, ast.Subscript) and
                         isinstance(tgt.value, ast.Attribute))):
                    # `self.x = v` / `self.cache[k] = v` escape; a
                    # LOCAL container (`out[i] = v`) stays in scope —
                    # its own escape is the function's return contract
                    base = tgt if isinstance(tgt, ast.Attribute) \
                        else tgt.value
                    where = dotted(base) or "container"
                    out.append(Finding(
                        sf.path, node.lineno, "view-escape",
                        f"{lbl} view stored on {where}: it outlives "
                        f"its dispatch scope while the source buffer "
                        f"gets recycled — materialize with bytes()/"
                        f".tobytes() or keep the view local",
                        end_line=node.end_lineno or 0))
            elif isinstance(node, ast.For) and \
                    isinstance(node.target, ast.Name) and \
                    terminal_name(node.iter) in _SEGMENT_ATTRS:
                tracked[node.target.id] = "frame-seg"
            elif isinstance(node, ast.Expr) and \
                    isinstance(node.value, ast.Call):
                call = node.value
                if isinstance(call.func, ast.Attribute) and \
                        call.func.attr in ("append", "add") and \
                        isinstance(call.func.value, ast.Attribute) and \
                        len(call.args) == 1:
                    lbl = _value_label(call.args[0], tracked)
                    if lbl is not None and \
                            not _is_materialized(call.args[0]):
                        where = dotted(call.func.value) or "container"
                        out.append(Finding(
                            sf.path, node.lineno, "view-escape",
                            f"{lbl} view appended to {where}: the "
                            f"container outlives the dispatch scope "
                            f"while the source buffer gets recycled — "
                            f"materialize before storing",
                            end_line=node.end_lineno or 0))
            elif isinstance(node, ast.Return) and node.value is not None:
                lbl = _value_label(node.value, tracked)
                if lbl is not None and not _is_materialized(node.value):
                    out.append(Finding(
                        sf.path, node.lineno, "view-escape",
                        f"{lbl} view returned from {fn.name}(): the "
                        f"caller holds a window onto a buffer this "
                        f"scope no longer controls — materialize, or "
                        f"document the refcount contract with a "
                        f"justified disable",
                        end_line=node.end_lineno or 0))
    return out
