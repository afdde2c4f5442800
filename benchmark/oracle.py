"""The plain reference: the store's semantics as straightforward host
Python, independent of the program.

Copied from ``crdt_tpu/oracle/replica.py`` (its quirks-off path, which is
the semantics the served store implements) so that a later PR cannot
change the yardstick by changing the program's oracle.  An op is
identified by ``(ts, rid, seq)``; replicas merge by set union of their
logs; the view folds each key newest -> oldest: the newest op seeds the
value, and every further op adds to it while both sides parse as
integers (PN-counter for integer strings, LWW register otherwise).
"""
from __future__ import annotations

from typing import Dict, Iterable, Optional, Tuple

INT64_MIN, INT64_MAX = -(2**63), 2**63 - 1

Ident = Tuple[int, int, int]


def atoi(s: str) -> Optional[int]:
    """Go's strconv.Atoi on a 64-bit platform (optional sign + digits,
    bounded to int64); None when it would return an error."""
    if not s:
        return None
    body = s[1:] if s[0] in "+-" else s
    if not body or not body.isascii() or not body.isdigit():
        return None
    v = int(s)
    return v if INT64_MIN <= v <= INT64_MAX else None


class OracleReplica:
    """One replica: a grow-only op log ``(ts, rid, seq) -> {key: value}``."""

    def __init__(self, rid: int = 0):
        self.rid = rid
        self.log: Dict[Ident, Dict[str, str]] = {}

    def add(self, ident: Ident, cmd: Dict[str, str]) -> None:
        self.log.setdefault(ident, dict(cmd))

    def merge(self, other: "OracleReplica") -> None:
        """Anti-entropy: adopt every op of ``other`` this log lacks."""
        for ident, cmd in other.log.items():
            self.log.setdefault(ident, dict(cmd))

    def state(self) -> Dict[str, str]:
        """The materialized view (newest -> oldest fold)."""
        return fold(self.log.items())


def fold(ops: Iterable[Tuple[Ident, Dict[str, str]]]) -> Dict[str, str]:
    state: Dict[str, str] = {}
    for _, cmd in sorted(ops, key=lambda kv: kv[0], reverse=True):
        for k, v in cmd.items():
            if k not in state:
                state[k] = v
                continue
            curr, change = atoi(state[k]), atoi(v)
            if curr is None or change is None:
                continue
            state[k] = str(curr + change)
    return state


def converged_state(replicas: Iterable[OracleReplica]) -> Dict[str, str]:
    """The view every replica reaches once gossip has delivered every op:
    the fold over the union of all logs."""
    union: Dict[Ident, Dict[str, str]] = {}
    for r in replicas:
        for ident, cmd in r.log.items():
            union.setdefault(ident, cmd)
    return fold(union.items())


def mismatches(got: Optional[Dict[str, str]], want: Dict[str, str],
               ignore: Iterable[str] = ()) -> int:
    """Keys on which a served view differs from the reference: missing,
    extra or with another value.  ``ignore`` names keys whose expected
    value is unknown (a write whose request failed without an answer)."""
    if got is None:
        return max(1, len(want))
    skip = set(ignore)
    keys = (set(got) | set(want)) - skip
    return sum(1 for k in keys if got.get(k) != want.get(k))
