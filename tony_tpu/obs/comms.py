"""Comms ledger: what the compiled program moves over ICI/DCN.

Every parallelism decision this repo will ever prove (Megatron-style
scaling, arXiv:2104.04473) comes down to the compute/collective split of
the step — so the collective set must be a first-class, always-derivable
fact, not something eyeballed out of an HLO dump. This module extracts it
from the artifacts the compile ledger (obs/compiles.py) already holds:
every AOT-compiled entry point's optimized HLO names its collectives with
their result shapes and replica groups, and :func:`extract_collectives`
turns that text into rows — op name, kind, payload bytes, replica groups.

The rows pair with *measured* time two ways:

- the anatomy report (obs/anatomy.py) matches captured device-trace events
  to the rows BY OP NAME (XLA names its trace events after the HLO ops —
  ``all-reduce.1`` in the HLO is ``all-reduce.1`` on the timeline), giving
  achieved bandwidth per collective and the compute-overlap fraction;
- ``cost_analysis()`` bytes ride the ledger entry for a static
  cross-check.

Stdlib-only on purpose: the extraction runs in the process that compiled
(duck-typed ``compiled.as_text()``), and the read paths run in deviceless
CLI processes on ledger snapshots.
"""

from __future__ import annotations

import re
from typing import Any

# HLO op kinds that move data between participants. Async forms
# (``all-reduce-start`` / ``-done``) normalise onto the base kind; the
# ``-done`` half is skipped (same transfer, already counted at start).
COLLECTIVE_KINDS = (
    "all-reduce",
    "all-gather",
    "reduce-scatter",
    "all-to-all",
    "ragged-all-to-all",
    "collective-permute",
    "collective-broadcast",
)

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s16": 2, "u16": 2, "s32": 4, "u32": 4,
    "s64": 8, "u64": 8, "f16": 2, "bf16": 2, "f32": 4, "f64": 8,
    "c64": 8, "c128": 16, "f8e4m3fn": 1, "f8e5m2": 1, "f8e4m3b11fnuz": 1,
    "f8e4m3fnuz": 1, "f8e5m2fnuz": 1, "s4": 1, "u4": 1,
}

# `%all-reduce.1 = f32[1,128]{1,0} all-reduce(...), channel_id=1, ...`
# and the tuple-result / ROOT / async-start variants
_OP_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?(?P<name>[\w.\-]+)\s*=\s*"
    r"(?P<type>\([^)]*\)|[\w\[\],]+(?:\{[^}]*\})?)\s*"
    r"(?P<op>[\w\-]+)\("
)
_SHAPE_RE = re.compile(r"(?P<dtype>[a-z][a-z0-9]*)\[(?P<dims>[0-9,]*)\]")
_GROUPS_RE = re.compile(
    r"replica_groups=(?P<g>\{\{[^}]*(?:\},\{[^}]*)*\}\}"
    r"|\[[^\]]*\](?:<=\[[^\]]*\])?)"
)


def _kind_of(op: str) -> str | None:
    """Normalised collective kind of an HLO opcode ('' for -done halves,
    None for non-collectives)."""
    base = op
    for suffix in ("-start", "-done"):
        if base.endswith(suffix):
            base = base[: -len(suffix)]
            if base in COLLECTIVE_KINDS:
                return "" if suffix == "-done" else base
            return None
    return base if base in COLLECTIVE_KINDS else None


def shape_bytes(type_text: str) -> int:
    """Total byte size of an HLO result type ('f32[1,128]{1,0}' or a
    tuple '(f32[...], u32[...])'); unknown dtypes count 0 rather than
    guessing."""
    total = 0
    for m in _SHAPE_RE.finditer(type_text):
        size = _DTYPE_BYTES.get(m.group("dtype"))
        if size is None:
            continue
        n = 1
        dims = m.group("dims")
        if dims:
            for d in dims.split(","):
                n *= int(d)
        total += n * size
    return total


def _parse_groups(raw: str) -> list[list[int]] | str:
    """``{{0,1},{2,3}}`` parses to [[0,1],[2,3]]; the iota form
    (``[2,2]<=[4]``) stays a string — it is already compact and exact."""
    if not raw.startswith("{{"):
        return raw
    try:
        return [
            [int(x) for x in grp.split(",") if x != ""]
            for grp in re.findall(r"\{([0-9,\s]*)\}", raw[1:-1])
        ]
    except ValueError:
        return raw


def extract_collectives(compiled: Any) -> list[dict[str, Any]]:
    """Collective rows of one compiled executable (or raw HLO text):
    ``{"name", "kind", "bytes", "result_type", "replica_groups"}`` per
    static HLO op, in program order. ``bytes`` is the result payload —
    for an all-gather that is the post-gather size, for a reduce-scatter
    the post-scatter shard; the per-kind wire cost model lives with the
    bandwidth math in obs/anatomy.py, not here."""
    if isinstance(compiled, str):
        text = compiled
    else:
        try:
            text = compiled.as_text()
        except (AttributeError, NotImplementedError, RuntimeError):
            return []  # not a compiled executable / backend keeps no text
    rows: list[dict[str, Any]] = []
    for line in text.splitlines():
        if "(" not in line or "=" not in line:
            continue
        m = _OP_RE.match(line)
        if m is None:
            continue
        kind = _kind_of(m.group("op"))
        if not kind:  # None (not a collective) or '' (-done half)
            continue
        gm = _GROUPS_RE.search(line)
        rows.append({
            "name": m.group("name"),
            "kind": kind,
            "bytes": shape_bytes(m.group("type")),
            "result_type": m.group("type"),
            "replica_groups": _parse_groups(gm.group("g")) if gm else "",
        })
    return rows


__all__ = ["COLLECTIVE_KINDS", "extract_collectives", "shape_bytes"]
