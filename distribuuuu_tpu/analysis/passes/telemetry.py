"""Telemetry-kind discipline pass — ``tools/check_telemetry_schema.py``
absorbed into the analysis framework (ISSUE 14 satellite).

Same checks, same message text, new findings plumbing: every emit call
site in the package (``metrics_log`` / ``emit_event`` / ``mirror_event``
/ ``timeline_log`` / ``emit_span``) must use a literal kind that is
declared in ``telemetry/schema.py`` with its required fields statically
present (or splatted), and only the sink modules may forward a dynamic
kind. Program spans are held to the same discipline: a literal name
passed to ``span`` / ``emit_span`` / ``annotate`` must be declared in
``schema.SPANS`` (the table that gives it its ``dtpu.<layer>.<name>``
profiler annotation). The old CLI remains as a thin wrapper over
:func:`check_file` / :func:`check_tree`, which keep their historical
``(violations, seen)`` string API — existing invocations and tests work
unchanged.
"""

from __future__ import annotations

import ast
import os

from distribuuuu_tpu.analysis.findings import Finding, finding_key

PASS_ID = "telemetry"

# emit surface -> implicit kind (None = first positional arg is the kind)
EMIT_FUNCS = {
    "metrics_log": None,
    "emit_event": None,
    "mirror_event": None,
    "timeline_log": "timeline",
    "emit_span": "span",
}

# span surface: the first positional argument is the span's name
SPAN_FUNCS = ("span", "emit_span", "annotate")

# modules allowed to forward a caller's kind variable (the sinks themselves)
DYNAMIC_KIND_OK = ("utils/jsonlog.py", "telemetry/spans.py")


def _func_name(call: ast.Call) -> str | None:
    f = call.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return None


def _finding(where: str, kind_coord: str, message: str) -> Finding:
    return Finding(
        pass_id=PASS_ID, severity="error", location=where,
        message=message,
        waiver_key=finding_key(
            PASS_ID, where.split(":")[0], kind_coord
        ),
    )


def check_file(path: str, rel: str) -> tuple[list, set]:
    """(findings, kinds_seen) for one source file."""
    from distribuuuu_tpu.telemetry import schema

    with open(path) as f:
        tree = ast.parse(f.read(), filename=rel)
    findings, seen = [], set()
    for node in ast.walk(tree):
        if not isinstance(node, ast.Call):
            continue
        name = _func_name(node)
        if name not in EMIT_FUNCS and name not in SPAN_FUNCS:
            continue
        where = f"{rel}:{node.lineno}"
        if name in SPAN_FUNCS and node.args:
            first = node.args[0]
            if (
                isinstance(first, ast.Constant)
                and isinstance(first.value, str)
                and first.value not in schema.SPANS
            ):
                findings.append(_finding(
                    where, f"span-{first.value}",
                    f"undeclared span name {first.value!r} — declare it "
                    "(with its layer) in SPANS of "
                    "distribuuuu_tpu/telemetry/schema.py",
                ))
        if name not in EMIT_FUNCS:
            continue
        kind = EMIT_FUNCS[name]
        if kind is None:
            if not node.args:
                continue  # not an emit form we recognize
            first = node.args[0]
            if isinstance(first, ast.Constant) and isinstance(
                first.value, str
            ):
                kind = first.value
            else:
                if not rel.replace(os.sep, "/").endswith(DYNAMIC_KIND_OK):
                    findings.append(_finding(
                        where, f"dynamic-{name}",
                        f"{name}() with a non-literal kind — only "
                        f"the sink modules {DYNAMIC_KIND_OK} may forward "
                        "a dynamic kind",
                    ))
                continue
        seen.add(kind)
        if kind not in schema.KINDS:
            findings.append(_finding(
                where, kind,
                f"undeclared kind {kind!r} — declare it (with "
                "required fields) in distribuuuu_tpu/telemetry/schema.py",
            ))
            continue
        if name in ("timeline_log", "emit_span"):
            continue  # those wrappers provide the required fields
        has_splat = any(kw.arg is None for kw in node.keywords)
        static = {kw.arg for kw in node.keywords if kw.arg is not None}
        missing = schema.KINDS[kind] - static
        if missing and not has_splat:
            findings.append(_finding(
                where, kind,
                f"kind {kind!r} drifted — call no longer provides "
                f"required fields {sorted(missing)} "
                "(telemetry/schema.py declares them)",
            ))
    return findings, seen


def check_tree(root: str) -> tuple[list, set]:
    """(findings, kinds_seen) for a package tree."""
    findings, seen = [], set()
    for dirpath, _dirnames, filenames in os.walk(root):
        if "__pycache__" in dirpath:
            continue
        for fn in sorted(filenames):
            if not fn.endswith(".py"):
                continue
            path = os.path.join(dirpath, fn)
            rel = os.path.relpath(path, os.path.dirname(root))
            v, s = check_file(path, rel)
            findings += v
            seen |= s
    return findings, seen


def run(repo: str) -> list:
    findings, _seen = check_tree(
        os.path.join(repo, "distribuuuu_tpu")
    )
    return findings
