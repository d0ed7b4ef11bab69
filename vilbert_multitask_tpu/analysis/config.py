"""``[tool.vmtlint]`` configuration from pyproject.toml.

This interpreter is Python 3.10 with no tomllib/tomli available, so a
minimal TOML-subset parser lives here — sections, string/bool/int values,
and (possibly multiline) arrays of strings cover everything the vmtlint
block needs. It is NOT a general TOML parser and only ever reads the
``tool.vmtlint`` tables.
"""

from __future__ import annotations

import dataclasses
import os
import re
from typing import Dict, List, Optional, Tuple


@dataclasses.dataclass
class VmtlintConfig:
    # Default scan roots when the CLI gets no paths.
    paths: List[str] = dataclasses.field(default_factory=lambda: [
        "vilbert_multitask_tpu", "scripts"])
    # Path fragments to skip entirely (matched against the forward-slash
    # relative path, substring semantics).
    exclude: List[str] = dataclasses.field(default_factory=list)
    # Roots treated as library code for library_only rules (stray-print).
    library_roots: List[str] = dataclasses.field(default_factory=lambda: [
        "vilbert_multitask_tpu"])
    # Checked-in baseline of grandfathered findings (repo-root relative).
    baseline: Optional[str] = None
    # Findings at/above this severity fail the run without --strict.
    fail_on: str = "error"
    # Per-rule severity overrides: {"VMT105": "error", ...}
    severity: Dict[str, str] = dataclasses.field(default_factory=dict)
    # Layering contracts ([tool.vmtlint.layers] forbid = ["A -> B", ...]):
    # modules under prefix A must not import modules under prefix B.
    layers: List[Tuple[str, str]] = dataclasses.field(default_factory=list)
    # Per-rule path exclusions ([tool.vmtlint.rule_paths]): rel-path
    # prefixes a rule skips — {"VMT107": ["tests"], ...}.
    rule_paths: Dict[str, List[str]] = dataclasses.field(default_factory=dict)


_SECTION_RE = re.compile(r"^\s*\[([^\]]+)\]\s*$")
_KEY_RE = re.compile(r"^\s*([A-Za-z0-9_\-\.]+)\s*=\s*(.*)$")
_STR_RE = re.compile(r'''^(?:"([^"]*)"|'([^']*)')$''')


def _strip_comment(line: str) -> str:
    """Drop a # comment that is not inside a string literal."""
    out, quote = [], None
    for ch in line:
        if quote:
            if ch == quote:
                quote = None
        elif ch in "\"'":
            quote = ch
        elif ch == "#":
            break
        out.append(ch)
    return "".join(out).rstrip()


def _parse_value(raw: str):
    raw = raw.strip()
    m = _STR_RE.match(raw)
    if m:
        return m.group(1) if m.group(1) is not None else m.group(2)
    if raw in ("true", "false"):
        return raw == "true"
    if raw.startswith("[") and raw.endswith("]"):
        inner = raw[1:-1].strip()
        if not inner:
            return []
        return [_parse_value(part) for part in _split_array(inner)]
    try:
        return int(raw)
    except ValueError:
        return raw  # tolerate; unknown shapes are ignored by the consumer


def _split_array(inner: str) -> List[str]:
    parts, cur, quote = [], [], None
    for ch in inner:
        if quote:
            cur.append(ch)
            if ch == quote:
                quote = None
        elif ch in "\"'":
            quote = ch
            cur.append(ch)
        elif ch == ",":
            if "".join(cur).strip():
                parts.append("".join(cur).strip())
            cur = []
        else:
            cur.append(ch)
    if "".join(cur).strip():
        parts.append("".join(cur).strip())
    return parts


def parse_toml_tables(text: str) -> Dict[str, Dict[str, object]]:
    """{section: {key: value}} for the TOML subset described above."""
    tables: Dict[str, Dict[str, object]] = {}
    section = ""
    lines = text.splitlines()
    i = 0
    while i < len(lines):
        line = _strip_comment(lines[i])
        i += 1
        if not line.strip():
            continue
        m = _SECTION_RE.match(line)
        if m:
            section = m.group(1).strip()
            tables.setdefault(section, {})
            continue
        m = _KEY_RE.match(line)
        if not m:
            continue
        key, raw = m.group(1), m.group(2).strip()
        # Multiline array: keep consuming until brackets balance.
        while raw.count("[") > raw.count("]") and i < len(lines):
            raw += " " + _strip_comment(lines[i]).strip()
            i += 1
        tables.setdefault(section, {})[key] = _parse_value(raw)
    return tables


def find_pyproject(start: str) -> Optional[str]:
    cur = os.path.abspath(start)
    while True:
        cand = os.path.join(cur, "pyproject.toml")
        if os.path.isfile(cand):
            return cand
        nxt = os.path.dirname(cur)
        if nxt == cur:
            return None
        cur = nxt


def load_config(start: str = ".") -> Tuple[VmtlintConfig, Optional[str]]:
    """(config, repo_root). Falls back to defaults with root=start when no
    pyproject.toml is found walking up from ``start``."""
    cfg = VmtlintConfig()
    pyproject = find_pyproject(start)
    if pyproject is None:
        return cfg, None
    with open(pyproject, "r", encoding="utf-8") as f:
        tables = parse_toml_tables(f.read())
    main = tables.get("tool.vmtlint", {})
    for key in ("paths", "exclude", "library_roots"):
        val = main.get(key)
        if isinstance(val, list):
            setattr(cfg, key, [str(v) for v in val])
    if isinstance(main.get("baseline"), str):
        cfg.baseline = main["baseline"]
    if main.get("fail_on") in ("error", "warning"):
        cfg.fail_on = main["fail_on"]
    sev = tables.get("tool.vmtlint.severity", {})
    cfg.severity = {k: str(v) for k, v in sev.items()
                    if v in ("error", "warning")}
    layers = tables.get("tool.vmtlint.layers", {}).get("forbid")
    if isinstance(layers, list):
        cfg.layers = [c for c in (parse_layer_contract(str(v))
                                  for v in layers) if c is not None]
    for key, val in tables.get("tool.vmtlint.rule_paths", {}).items():
        if isinstance(val, list):
            cfg.rule_paths[key] = [str(v) for v in val]
    return cfg, os.path.dirname(pyproject)


def parse_layer_contract(spec: str) -> Optional[Tuple[str, str]]:
    """``"pkg.models -> pkg.serve"`` → ("pkg.models", "pkg.serve").
    Path-style prefixes (``pkg/models``) are normalized to dotted form."""
    if "->" not in spec:
        return None
    src, _, dst = spec.partition("->")

    def norm(s: str) -> str:
        return s.strip().strip("/").replace("/", ".")

    src, dst = norm(src), norm(dst)
    return (src, dst) if src and dst else None
