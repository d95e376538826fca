"""Host arrays written once into fresh pages, in row tiles, by threads.

Two fills on a build's critical path write a large array the device then
waits for: the assembler's float64 matrix (``frame/feature.py``) and the
array the H2D stage ships (``parallel/sharding.py``). Most of such a
fill's time on the chip's host is the first touch of the fresh pages,
which threads take side by side, and numpy's copies and casts leave the
interpreter lock while they run. So both share one rule: a fill of many
row tiles is shared out among ``FILL_THREADS`` threads, each a run of
whole tiles, and a small one stays on the calling thread.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Callable

# A tile is at most TILE_BYTES, so what a thread writes between two
# visits to the interpreter stays in a core's cache. FILL_THREADS is the
# best of what was measured on a TPU v5e host of 13 cores.
TILE_BYTES = 1 << 20
FILL_THREADS = 4


def fill_row_tiles(
    rows: int, tile_rows: int, fill_rows: Callable[[int, int], None]
) -> tuple[int, int]:
    """Call ``fill_rows(first_row, last_row)`` over rows ``[0, rows)``,
    each call a run of whole tiles of ``tile_rows`` (the last may be
    short). Several threads take the runs where each would get several
    tiles; else one call on this thread. Returns ``(threads, tiles)``:
    how many calls filled the rows and how many tiles they hold."""
    tiles = -(-rows // tile_rows)
    threads = min(FILL_THREADS, tiles // FILL_THREADS)  # several tiles each
    if threads <= 1:
        fill_rows(0, rows)
        return 1, tiles
    share = -(-tiles // threads) * tile_rows
    starts = range(0, rows, share)
    with ThreadPoolExecutor(len(starts)) as pool:
        shares = [
            pool.submit(fill_rows, first_row, min(rows, first_row + share))
            for first_row in starts
        ]
        for done in shares:
            done.result()
    return len(starts), tiles
