"""Published peaks per device kind, from ``bench/peaks.json``.

A device kind missing from the table is an error, never a default: a share
of a peak that nobody published means nothing.
"""
from __future__ import annotations

import json
from pathlib import Path

from .spec import ROOT

__all__ = ["peaks"]


def peaks(device_kind: str, root: Path = ROOT) -> dict:
    with open(Path(root) / "bench" / "peaks.json") as f:
        table = json.load(f)
    if device_kind not in table["kinds"]:
        raise KeyError(f"no published peaks for device kind {device_kind!r} "
                       f"in bench/peaks.json (known: {sorted(table['kinds'])})")
    return table["kinds"][device_kind]
