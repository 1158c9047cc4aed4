"""Doctor↔knob sync checker.

A doctor finding's ``suggestion`` string is what the operator reading a
dossier or a ledger line acts on, so it must name something that
exists:

  * **unactionable-suggestion** (error): every ``Finding(...)``
    constructed in ``runtime/doctor.py`` must name at least one declared
    Knob as ``conf.<name>`` in its suggestion, and every ``conf.<name>``
    it mentions must resolve in the ``KNOBS`` registry. A typo'd or
    free-form suggestion misleads the operator reading the dossier.

The knob registry is loaded by executing ``config.py`` standalone (the
knob-registry checker's posture — never ``import blaze_tpu``).
"""

from __future__ import annotations

import ast
import re
from pathlib import Path
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from tools.blazelint.core import (Checker, Finding, ModuleInfo, call_name,
                                  load_config_module)

DOCTOR_REL = "blaze_tpu/runtime/doctor.py"

_KNOB_RE = re.compile(r"conf\.([a-z0-9_]+)")


def _static_text(node: ast.AST) -> str:
    """Best-effort static text of a suggestion expression: plain (and
    implicitly concatenated) literals come back whole; f-strings and
    ``+``/``%``/``.format`` constructions contribute their literal parts
    — enough to see every ``conf.<name>`` mention, which doctor never
    builds dynamically."""
    parts: List[str] = []
    for sub in ast.walk(node):
        if isinstance(sub, ast.Constant) and isinstance(sub.value, str):
            parts.append(sub.value)
    return "".join(parts)


class DoctorKnobSync(Checker):
    name = "doctor-knob-sync"

    def __init__(self, root: Optional[Path] = None,
                 knobs: Optional[Dict[str, object]] = None,
                 config_rel: str = "blaze_tpu/config.py") -> None:
        if knobs is None:
            assert root is not None
            knobs = dict(load_config_module(root / config_rel).KNOBS)
        self.knobs = knobs
        self._suggestions: List[Tuple[ModuleInfo, ast.Call, str]] = []

    # -- per module --------------------------------------------------------

    def check_module(self, mod: ModuleInfo) -> Iterable[Finding]:
        if mod.rel != DOCTOR_REL:
            return ()
        for node in ast.walk(mod.tree):
            if not isinstance(node, ast.Call) or \
                    call_name(node) != "Finding":
                continue
            sugg: Optional[ast.AST] = None
            if len(node.args) >= 4:
                sugg = node.args[3]
            for kw in node.keywords:
                if kw.arg == "suggestion":
                    sugg = kw.value
            if sugg is not None:
                self._suggestions.append((mod, node, _static_text(sugg)))
        return ()

    # -- finalize ----------------------------------------------------------

    def finalize(self, modules: Sequence[ModuleInfo]) -> Iterable[Finding]:
        findings: List[Finding] = []
        for mod, node, text in self._suggestions:
            names = _KNOB_RE.findall(text)
            declared = [n for n in names if n in self.knobs]
            for n in names:
                if n not in self.knobs:
                    findings.append(Finding(
                        checker=self.name, rule="unactionable-suggestion",
                        path=mod.rel, line=node.lineno, severity="error",
                        message=(f"Finding suggestion mentions "
                                 f"conf.{n}, which is not a declared "
                                 f"knob in config.KNOBS"),
                        symbol=n))
            if not declared:
                findings.append(Finding(
                    checker=self.name, rule="unactionable-suggestion",
                    path=mod.rel, line=node.lineno, severity="error",
                    message=("Finding suggestion names no declared "
                             "conf.<knob> — the 3am operator cannot "
                             "act on it"),
                    symbol="suggestion"))
        return findings
