"""env-registry: every MXTPU_* knob is typed, central, and documented.

Three invariants, one choke point (``mxnet_tpu/env.py``):

  1. library code (``mxnet_tpu/``) never reads an ``MXTPU_*`` name through
     raw ``os.environ`` / ``os.getenv`` — it goes through the typed
     accessors (``env.get`` / ``env.raw`` / ``env.is_set``), so type,
     default and doc live in exactly one place;
  2. every name the code reads — via the accessors in the library, or via
     ``os.environ`` literals in ``tools/`` (which stay import-free of
     the package) — is declared in the registry;
  3. the registry and the ``docs/env_vars.md`` Framework table agree
     exactly, both directions (the table is generated:
     ``python -m mxnet_tpu.env --markdown``).

All checks are AST/text-level — the lint never imports mxnet_tpu.
"""
from __future__ import annotations

import ast
import re

from .. import Finding
from ..astutil import dotted, str_const

_REGISTRY_FILE = "mxnet_tpu/env.py"
_DOCS_FILE = "docs/env_vars.md"
_VAR_RE = re.compile(r"MXTPU_[A-Z0-9_]+")
_ACCESSORS = {"get", "raw", "is_set"}


def registered_names(repo):
    """Names declared by ``_var(...)`` calls in mxnet_tpu/env.py (AST)."""
    tree = repo.tree(_REGISTRY_FILE)
    names = []
    if tree is None:
        return names
    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and dotted(node.func) == "_var" \
                and node.args:
            name = str_const(node.args[0])
            if name:
                names.append(name)
    return names


def documented_names(repo):
    """MXTPU names in the first cell of docs/env_vars.md Framework rows."""
    text = repo.read(_DOCS_FILE) or ""
    names, in_section = [], False
    for line in text.splitlines():
        if line.startswith("## "):
            in_section = line.strip() == "## Framework (`MXTPU_*`)"
            continue
        if not in_section or not line.startswith("|"):
            continue
        first_cell = line.split("|")[1] if line.count("|") >= 2 else ""
        names.extend(_VAR_RE.findall(first_cell))
    return names


def _environ_read_name(node):
    """The MXTPU_* literal read by this node via raw os.environ/getenv,
    or None."""
    if isinstance(node, ast.Call):
        cname = dotted(node.func) or ""
        if cname.endswith("environ.get") or cname in ("os.getenv",
                                                      "getenv"):
            if node.args:
                name = str_const(node.args[0])
                if name and name.startswith("MXTPU_"):
                    return name
    if isinstance(node, ast.Subscript) and isinstance(node.ctx, ast.Load):
        vname = dotted(node.value) or ""
        if vname == "environ" or vname.endswith(".environ"):
            name = str_const(node.slice)
            if name and name.startswith("MXTPU_"):
                return name
    return None


def _accessor_read_name(node):
    """The MXTPU_* literal read via an env-registry accessor call
    (``env.get("...")`` / ``_env.raw("...")`` / ...), or None."""
    if isinstance(node, ast.Call) and isinstance(node.func, ast.Attribute) \
            and node.func.attr in _ACCESSORS and node.args:
        base = dotted(node.func.value) or ""
        if base == "env" or base.endswith("env") or base.endswith("env_mod"):
            name = str_const(node.args[0])
            if name and name.startswith("MXTPU_"):
                return name
    return None


class EnvRegistryChecker:
    rule = "env-registry"
    description = ("MXTPU_* reads go through mxnet_tpu.env; registry and "
                   "docs/env_vars.md agree")

    def run(self, repo):
        registered = set(registered_names(repo))
        if not registered:
            yield Finding(self.rule, _REGISTRY_FILE, 1,
                          "no _var(...) declarations found — the typed "
                          "env registry is empty or unparseable")
            return

        # 1+2: library files use accessors; accessor names are registered
        for rel in repo.py_files("mxnet_tpu"):
            if rel == _REGISTRY_FILE:
                continue
            tree = repo.tree(rel)
            if tree is None:
                continue
            for node in ast.walk(tree):
                name = _environ_read_name(node)
                if name is not None:
                    yield Finding(
                        self.rule, rel, node.lineno,
                        "raw environ read of `%s` — library code reads "
                        "MXTPU_* through mxnet_tpu.env (get/raw/is_set)"
                        % name)
                    continue
                name = _accessor_read_name(node)
                if name is not None and name not in registered:
                    yield Finding(
                        self.rule, rel, node.lineno,
                        "`%s` is read via mxnet_tpu.env but not declared "
                        "in its registry (KeyError at runtime)" % name)

        # 2: tools read MXTPU_* names that must be registered
        for rel in repo.py_files("tools"):
            tree = repo.tree(rel)
            if tree is None:
                continue
            for node in ast.walk(tree):
                name = _environ_read_name(node)
                if name is not None and name not in registered:
                    yield Finding(
                        self.rule, rel, node.lineno,
                        "`%s` is read here but missing from the "
                        "mxnet_tpu/env.py registry (undocumented knob)"
                        % name)

        # 3: registry <-> docs parity, both directions
        documented = set(documented_names(repo))
        for name in sorted(registered - documented):
            yield Finding(
                self.rule, _DOCS_FILE, 1,
                "`%s` is in the mxnet_tpu/env.py registry but missing "
                "from the docs/env_vars.md Framework table (regenerate: "
                "python -m mxnet_tpu.env --markdown)" % name)
        for name in sorted(documented - registered):
            yield Finding(
                self.rule, _DOCS_FILE, 1,
                "`%s` is documented in docs/env_vars.md but not declared "
                "in the mxnet_tpu/env.py registry" % name)
