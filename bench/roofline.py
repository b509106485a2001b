"""Bytes the sketch scan needs for one dispatch, from shapes and queries.

The scan reads, for the batch's distinct non-zero query coordinates:

* each distinct (sketch row, side) they touch, once: C cells of the cell
  type (the U side for q_j > 0, the L side for q_j < 0 when a lower sketch
  is stored; an upper-only index reads U for every coordinate);
* each distinct membership row, once: C bits;
* the C-slot active gate, once: C bytes.

The ``[B, C]`` score matrix that today's implementation writes is left out:
it is an artefact of one implementation, not work the algorithm needs, so
the count stays the same whatever implements the scan.  A scan that skips
tiles changes the work itself, and needs this count revised first.
"""

from __future__ import annotations

import numpy as np


def scan_bytes(q_idx, q_val, maps: np.ndarray, capacity: int,
               cell_bytes: int, signed: bool) -> int:
    """Bytes one dispatch of the queries ``[B, L]`` must read on one chip
    holding ``capacity`` slots.  ``maps`` is the int32[h, n] mapping table."""
    q_idx = np.asarray(q_idx)
    q_val = np.asarray(q_val)
    live = q_idx >= 0
    coords = np.unique(q_idx[live])
    sides = set()
    for j, v in zip(q_idx[live].tolist(), q_val[live].tolist()):
        side = 1 if (signed and v < 0) else 0
        for o in range(maps.shape[0]):
            sides.add((int(maps[o, j]), side))
    return (len(sides) * capacity * cell_bytes
            + len(coords) * capacity // 8
            + capacity)
