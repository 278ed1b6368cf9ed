"""The row-range sharding rule: which server holds which rows of a
table at creation (tables/matrix_table.py), and the layout a reshard
plans towards (runtime/shard_map.py, runtime/zoo.py)."""

from __future__ import annotations

from typing import List


def row_offsets(num_row: int, num_servers: int) -> List[int]:
    """Row ranges per server incl. the degenerate rows<servers layout
    (ref: matrix_table.cpp:24-41). Returns num_actual_servers+1 offsets."""
    offsets = [0]
    length = num_row // num_servers
    if length > 0:
        offset = length
        i = 0
        while length > 0 and offset < num_row and i + 1 < num_servers:
            offsets.append(offset)
            offset += length
            i += 1
    else:
        offset = 1
        i = 0
        while offset < num_row and i + 1 < num_servers:
            offsets.append(offset)
            offset += 1
            i += 1
    offsets.append(num_row)
    return offsets
