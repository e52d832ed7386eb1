"""Device time by the program's named scopes, from a profiler trace
(``.xplane.pb``).

Each device op's ``tf_op`` (its name stack, from the plane's event metadata
through ``harness.xplane``; ``ProfileData`` does not expose it) puts the op
under the innermost of ``SCOPES`` that appears as a path component, also
inside ``jvp(...)``, ``transpose(jvp(...))`` and other transform wrappers.
An op under none of them counts as ``(no scope)``.  Times are exclusive, as
``xtrace`` counts ops: an op inside a loop op is taken from the loop's.
"""

from __future__ import annotations

import re
from typing import Dict, List, Optional, Tuple

from harness import xplane, xtrace

SCOPES = ("embed", "attn", "mlp", "lm_head", "loss", "optimizer", "fold")
NO_SCOPE = "(no scope)"
NO_MODULE = "(no module)"


def scope_of(tf_op: str) -> str:
    """``jit(f)/vmap(transpose(jvp(attn)))/dot_general:`` -> ``attn``: the
    innermost path component that is one of ``SCOPES`` once transform
    wrappers are taken off; a ``jit(...)`` component names a function, not
    a scope."""
    for part in reversed(tf_op.rsplit(":", 1)[0].split("/")):
        m = re.fullmatch(r"((?:[\w.-]+\()*)([^()]*)\)*", part)
        if m and m.group(2) in SCOPES and "jit(" not in m.group(1):
            return m.group(2)
    return NO_SCOPE


def scoped_ops(path: str, plane: Optional[str] = None
               ) -> List[Tuple[int, int, Tuple[str, str]]]:
    """(start_ns, end_ns, (module, scope)) of each op execution on the
    device plane named ``plane`` (by default the first TPU plane with ops),
    in whole nanoseconds as ``ProfileData`` gives them."""
    planes = xplane.read(path, want=lambda n: (
        n == plane if plane else n.startswith(xtrace.DEVICE_PREFIX)))
    for p in planes:
        lines = [ln for ln in p.lines if ln.name == xtrace.OPS_LINE
                 and ln.events]
        if lines:
            break
    else:
        return []
    modules: Dict[int, str] = {}
    for name, _ in p.event_metadata.values():
        m = re.fullmatch(r"(.*)\((\d+)\)", name)
        if m:
            modules[int(m.group(2))] = m.group(1)
    label = {mid: (modules.get(stats.get("program_id"), NO_MODULE),
                   scope_of(str(stats.get("tf_op", ""))))
             for mid, (_, stats) in p.event_metadata.items()}
    out = []
    for ln in lines:
        for mid, off_ps, dur_ps in ln.events:
            s = ln.timestamp_ns + off_ps // 1000
            out.append((s, s + dur_ps // 1000,
                        label.get(mid, (NO_MODULE, NO_SCOPE))))
    return out


def scope_seconds(path: str, plane: Optional[str] = None
                  ) -> Dict[str, Dict[str, float]]:
    """Module -> scope -> exclusive device seconds on one chip."""
    out: Dict[str, Dict[str, float]] = {}
    for (mod, scope), ns in xtrace._exclusive(scoped_ops(path, plane)).items():
        out.setdefault(mod, {})[scope] = ns / 1e9
    return out


def totals(by_module: Dict[str, Dict[str, float]]) -> Dict[str, float]:
    """Scope -> seconds summed over the modules."""
    out: Dict[str, float] = {}
    for split in by_module.values():
        for scope, secs in split.items():
            out[scope] = out.get(scope, 0.0) + secs
    return out

