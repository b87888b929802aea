"""Bytes and operations one build of the dependency view NEEDS on the
device, from shapes only (the companion of ``lib/shapes.py`` for the
query side).

The view's device program (``query/readback.py:dep_edges_snapshot``,
module ``jit_dep_edges_snapshot``) turns the edge slab into the columns
the host reads back, live or not, every row once:

  read   the slab's eight columns: key table (hi, lo)            8 B
         caller id (hi, lo), callee id (hi, lo)                 16 B
         caller-is-service flag                                  1 B
         the (nconn, bytes) counter pair                         8 B
  write  what it returns: live flag, the four id words, the
         caller flag, nconn, bytes                              26 B
Operations: two compares and an or a row for the live flag; nothing near
the compute roof. The count depends on ``runtime.dep_edge_capacity``
alone, so it stays valid however the program is written, as long as it
returns these columns in buffers of its own.
"""

from __future__ import annotations

READ_BYTES, WRITE_BYTES, ROW_OPS = 8 + 16 + 1 + 8, 1 + 16 + 1 + 4 + 4, 3


def view_needs(runtime: dict) -> dict:
    """→ bytes and operations of one build over the whole edge slab."""
    rows = int(runtime["dep_edge_capacity"])
    return {"bytes": rows * (READ_BYTES + WRITE_BYTES),
            "ops": rows * ROW_OPS}
