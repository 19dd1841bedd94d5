"""Compulsory HBM bytes of one dispatch, from the ring's sizes alone.

The counts use the widths the mathematics needs: residues and input
segments of ceil(v/8) bytes, output limbs of ceil(w/8) bytes.  They never
count the lanes or the layout of any implementation, so a roofline built
on them reads the same work whatever computes it.  For ``rows`` products:

* cascade (forward NTT, pointwise product, inverse NTT): two residue
  vectors in and one out per product, plus the forward and inverse
  twiddles, read once per dispatch;
* crt (the paper's pre- and post-processing, decompose + compose): the
  segments of both operands in, both operands' residues out of
  decompose, the product's residues into compose, its limbs out.
"""
from __future__ import annotations

import json
from pathlib import Path

PEAKS_FILE = Path(__file__).resolve().parent / "peaks.json"


def _bytes(bits: int) -> int:
    return -(-bits // 8)


def cascade_bytes(n: int, t: int, v: int, rows: int) -> int:
    r = _bytes(v)
    return rows * 3 * t * n * r + 2 * t * n * r


def crt_bytes(n: int, t: int, v: int, S: int, L: int, w: int, rows: int) -> int:
    r = _bytes(v)
    return (rows * 2 * n * S * r + rows * 2 * t * n * r + rows * t * n * r
            + rows * n * L * _bytes(w))


def config_bytes(cfg: dict, rows: int) -> dict[str, int]:
    """Both counts for a configuration file's sizes."""
    n, t, v = cfg["n"], cfg["t"], cfg["v"]
    return {
        "cascade": cascade_bytes(n, t, v, rows),
        "crt": crt_bytes(n, t, v, cfg["seg_count"], cfg["limb_count"],
                         cfg["limb_bits"], rows),
    }


def peaks_for(device_kind: str, path: Path = PEAKS_FILE) -> dict:
    """The published peaks of ``device_kind``; a kind missing from the
    table is an error, never a default."""
    table = json.loads(Path(path).read_text())
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device kind {device_kind!r} in {path.name}; "
            f"known: {sorted(table)}"
        )
    return table[device_kind]
