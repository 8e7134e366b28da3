"""Tie a trace's device ops to the program's ``jax.named_scope`` spans.

A device trace names each op by its HLO instruction (``fusion.49``) and
drops the instruction's ``op_name``, which carries the scopes.  The
compiled program's text has both, so a unit that holds its compiled step
reads there which instructions run under which scope.
"""

from __future__ import annotations

import re

#: an instruction line of HLO text: its name, and the op_name it carries
INSTRUCTION = re.compile(
    r'^\s*(?:ROOT )?%(\S+) = [^\n]*?op_name="([^"]*)"', re.M)


def under(op_name: str, scope: str) -> bool:
    """Whether ``scope`` is a part of the ``op_name`` path, bare or inside a
    transformation (``transpose(jvp(repro.logsig.log))``)."""
    return re.search(rf"(^|[/(]){re.escape(scope)}([/)]|$)",
                     op_name) is not None


def scoped_ops(hlo_text: str, scopes) -> dict:
    """Per scope, the names of the instructions whose op_name passes it."""
    found = INSTRUCTION.findall(hlo_text)
    return {s: frozenset(name for name, op in found if under(op, s))
            for s in scopes}
