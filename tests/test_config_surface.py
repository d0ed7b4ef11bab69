"""The configuration surface stays as small as what reads it.

Three rules a later PR could break without any other test noticing:

- a config field nothing reads is an option nobody can measure: every
  field of the four dataclasses that carry settings (not published model
  shapes) is named somewhere in the package outside ``config.py``;
- the environment is not a second configuration: the variables the
  program and its entry points read are the listed few, all of them
  JAX's or XLA's own;
- a document that sends the reader to a file names one that exists.

Pure text and AST work: nothing here imports JAX.
"""

import ast
import dataclasses
import os
import re

import pytest

from vilbert_multitask_tpu import config as config_mod

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
PACKAGE = os.path.join(REPO, "vilbert_multitask_tpu")
ENTRY_POINTS = ("chip_smoke.py", "__graft_entry__.py")
# Run-time products and scratch: never what a document means by a file.
SKIP_DIRS = {".git", "__pycache__", ".scratch", "chiprun_out", ".jax_cache",
             ".aot_cache", ".pytest_cache", ".cache", "serve_state", "media"}

# The whole list. A new name here is a new way to configure the program:
# give it a ServingConfig / EngineConfig field or a flag instead.
ENV_KNOBS = {
    "JAX_COMPILATION_CACHE_DIR",   # engine/cachedir.py: where JAX says
    "JAX_COORDINATOR_ADDRESS",     # parallel/distributed.py: multi-host
    "JAX_NUM_PROCESSES",
    "JAX_PROCESS_ID",
    "XLA_FLAGS",                   # __graft_entry__.py: virtual devices
}


def _package_sources():
    for root, dirs, files in os.walk(PACKAGE):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        for name in files:
            if name.endswith(".py"):
                yield os.path.join(root, name)


def _read(path):
    with open(path, encoding="utf-8") as f:
        return f.read()


@pytest.mark.parametrize("cls", ["ServingConfig", "EngineConfig",
                                 "GenerateConfig", "MeshConfig"])
def test_every_field_has_a_reader(cls):
    text = "\n".join(_read(p) for p in _package_sources()
                     if os.path.abspath(p) != os.path.abspath(
                         config_mod.__file__))
    unread = [f.name for f in dataclasses.fields(getattr(config_mod, cls))
              if not re.search(rf"\b{re.escape(f.name)}\b", text)]
    assert not unread, (
        f"{cls} fields that nothing in the package reads: {unread}; "
        f"delete them or make them constants beside their reader")


def _environ_names(tree, strings):
    """Names this module reads from (or looks up in) ``os.environ``.
    ``strings`` resolves a module-level ``NAME = "..."`` used as the key."""
    def is_environ(node):
        return (isinstance(node, ast.Attribute) and node.attr == "environ"
                and isinstance(node.value, ast.Name)
                and node.value.id == "os")

    def key_of(node):
        if isinstance(node, ast.Constant) and isinstance(node.value, str):
            return node.value
        ident = (node.id if isinstance(node, ast.Name)
                 else node.attr if isinstance(node, ast.Attribute) else None)
        return strings.get(ident, f"<unresolved {ast.dump(node)}>")

    for node in ast.walk(tree):
        if isinstance(node, ast.Call) and node.args and isinstance(
                node.func, ast.Attribute):
            f = node.func
            if (f.attr in ("get", "pop", "setdefault")
                    and is_environ(f.value)):
                yield key_of(node.args[0])
            elif (f.attr == "getenv" and isinstance(f.value, ast.Name)
                  and f.value.id == "os"):
                yield key_of(node.args[0])
        elif isinstance(node, ast.Subscript) and is_environ(node.value):
            yield key_of(node.slice)
        elif isinstance(node, ast.Compare) and any(
                is_environ(c) for c in node.comparators):
            yield key_of(node.left)


def test_no_entry_point_reads_an_undocumented_env_knob():
    paths = list(_package_sources()) + [os.path.join(REPO, p)
                                        for p in ENTRY_POINTS]
    trees = {p: ast.parse(_read(p)) for p in paths}
    strings = {}
    for tree in trees.values():
        for node in tree.body:
            if (isinstance(node, ast.Assign) and len(node.targets) == 1
                    and isinstance(node.targets[0], ast.Name)
                    and isinstance(node.value, ast.Constant)
                    and isinstance(node.value.value, str)):
                strings[node.targets[0].id] = node.value.value
    found = {}
    for path, tree in trees.items():
        for name in _environ_names(tree, strings):
            found.setdefault(name, os.path.relpath(path, REPO))
    extra = {n: p for n, p in found.items() if n not in ENV_KNOBS}
    assert not extra, f"environment variables read outside the list: {extra}"
    assert set(found) == ENV_KNOBS, (
        f"listed but no longer read: {sorted(ENV_KNOBS - set(found))}")


def _repo_files():
    out = []
    for root, dirs, files in os.walk(REPO):
        dirs[:] = [d for d in dirs if d not in SKIP_DIRS]
        out.extend(os.path.relpath(os.path.join(root, n), REPO)
                   .replace(os.sep, "/") for n in files)
    return out


@pytest.mark.parametrize("doc", ["README.md", "ARCHITECTURE.md",
                                 ".claude/skills/verify/SKILL.md"])
def test_documents_name_files_that_exist(doc):
    files = _repo_files()
    missing = set()
    for span in re.findall(r"`([^`\n]+)`", _read(os.path.join(REPO, doc))):
        for path in re.findall(r"[\w./<>*{},-]+\.(?:py|sh|json)\b", span):
            if re.search(r"[<>*{}]", path) or path.startswith("/"):
                continue  # a pattern (`tests/tiny.<family>.json`) or a
                # file outside the checkout (`/root/TESTS_LAST_RUN.json`)
            path = path.lstrip("./")
            if not any(f == path or f.endswith("/" + path) for f in files):
                missing.add(path)
    assert not missing, (
        f"{doc} names files the checkout does not have: {sorted(missing)}")
