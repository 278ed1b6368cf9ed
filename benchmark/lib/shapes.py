"""Bytes a table request has to move, from its shapes alone.

These are the bytes the algorithm needs at the table's LOGICAL width
(50 columns, not the 128 lanes they are stored in), so a roofline share
computed from them can only understate the device's traffic and cannot
pass 100% by counting padding as useful work.
"""


def gather_bytes(rows: int, cols: int, value_bytes: int = 4,
                 id_bytes: int = 4) -> int:
    """Read ``rows`` ids, read that many table rows, write them out."""
    return rows * id_bytes + 2 * rows * cols * value_bytes


def scatter_add_bytes(rows: int, cols: int, value_bytes: int = 4,
                      id_bytes: int = 4) -> int:
    """Read ``rows`` ids and their deltas; read, add and write back that
    many table rows."""
    return rows * id_bytes + 3 * rows * cols * value_bytes


def roofline_share(needed_bytes: float, seconds: float,
                   peak_bytes_per_s: float) -> float:
    """Percent of the memory-bandwidth bound: the least time the chip
    could take over the time it took."""
    return 100.0 * (needed_bytes / peak_bytes_per_s) / seconds
