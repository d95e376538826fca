"""The bytes a histogram tree fit cannot avoid moving, from its shapes.

Counted per program call and kept deliberately lean, so that a share of
the roofline computed from it cannot pass 100 %: nothing is counted that
an implementation may fuse away or recompute.

- Per level the binned matrix (1 byte a cell) and the labels (1 byte a
  row) are read once, whatever the number of trees grown side by side
  in the call: they share it.
- Each tree reads and writes its rows' node index, 1 byte each (depth 5
  has 32 nodes), per level. Bootstrap weights can be redrawn from the
  key and are not counted.
- Only the first level has to see every row: from the second on, the
  smaller child's histogram and a subtraction give the larger one's, so
  half the rows are counted.
- A boosting round also reads and writes its margin, 4 bytes each.

- Binning reads the float32 matrix and writes the int8 one, once a
  build: the three tree classifiers bin the same matrix at the same
  thresholds and could share it.

Rows are the real training rows, not the padded bucket.
"""

from __future__ import annotations


def levels_of_rows(depth: int) -> float:
    return 1.0 + (depth - 1) / 2.0


def tree_fit_bytes(rows: int, features: int, depth: int, trees_side_by_side: int) -> float:
    """One call that grows ``trees_side_by_side`` trees level by level."""
    per_level = rows * (features + 1 + 2 * trees_side_by_side)
    return levels_of_rows(depth) * per_level


def boosting_bytes(rows: int, features: int, depth: int, rounds: int) -> float:
    """``rounds`` boosting rounds, one tree after another."""
    return rounds * (tree_fit_bytes(rows, features, depth, 1) + 8 * rows)


def binning_bytes(rows: int, features: int) -> float:
    """The float32 matrix read and the int8 bins written, once a build."""
    return rows * features * (4 + 1)
