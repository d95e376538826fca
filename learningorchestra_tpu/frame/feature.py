"""Feature stages: StringIndexer, VectorAssembler, Pipeline.

The `pyspark.ml.feature` subset the documented preprocessor example uses
(reference docs/model_builder.md): per-column label indexing and dense
feature assembly feeding the classifiers.
"""

from __future__ import annotations

from concurrent.futures import ThreadPoolExecutor
from typing import Optional

import numpy as np

from learningorchestra_tpu.frame.dataframe import DataFrame
from learningorchestra_tpu.frame.expressions import _is_null_array
from learningorchestra_tpu.telemetry import tracing as _tracing

ERROR = "error"
SKIP = "skip"
KEEP = "keep"


class StringIndexerModel:
    def __init__(self, input_col: str, output_col: str, labels: list, handle_invalid: str):
        self.inputCol = input_col
        self.outputCol = output_col
        self.labels = labels
        self._index = {label: float(code) for code, label in enumerate(labels)}
        self.handle_invalid = handle_invalid

    def transform(self, df: DataFrame) -> DataFrame:
        column = df._column(self.inputCol)
        codes = np.empty(len(column), dtype=np.float64)
        keep = np.ones(len(column), dtype=bool)
        for i, value in enumerate(column):
            code = self._index.get(value)
            if code is None:
                if self.handle_invalid == ERROR:
                    raise ValueError(
                        f"StringIndexer: unseen or null label {value!r} in "
                        f"column {self.inputCol!r}"
                    )
                if self.handle_invalid == SKIP:
                    keep[i] = False
                    code = np.nan
                else:  # keep: unseen bucket = num labels
                    code = float(len(self.labels))
            codes[i] = code
        out = df.withColumn(self.outputCol, codes)
        if self.handle_invalid == SKIP:
            return out._take(keep)
        return out


class StringIndexer:
    """Orders labels by descending frequency, ties broken
    lexicographically — Spark's default ``frequencyDesc`` order, so
    indexed features match the reference's encoding."""

    def __init__(
        self,
        inputCol: Optional[str] = None,
        outputCol: Optional[str] = None,
        handleInvalid: str = ERROR,
    ):
        self.inputCol = inputCol
        self.outputCol = outputCol or (f"{inputCol}_index" if inputCol else None)
        self.handleInvalid = handleInvalid

    def setHandleInvalid(self, value: str) -> "StringIndexer":
        self.handleInvalid = value
        return self

    def fit(self, df: DataFrame) -> StringIndexerModel:
        column = df._column(self.inputCol)
        nulls = _is_null_array(column)
        counts: dict = {}
        for value, is_null in zip(column, nulls):
            if is_null:
                continue
            counts[value] = counts.get(value, 0) + 1
        labels = sorted(counts, key=lambda v: (-counts[v], str(v)))
        return StringIndexerModel(
            self.inputCol, self.outputCol, labels, self.handleInvalid
        )


# One tile of the matrix and the staging block it is filled from are
# each at most _TILE_BYTES, so both stay in a core's cache while a tile
# is written. A tile is at most _TILE_MAX_COLUMNS wide and as tall as
# the bytes allow: every copy into the staging block is then one long
# contiguous run, made by numpy outside the interpreter lock, which is
# what lets _FILL_THREADS fill side by side.
_TILE_BYTES = 1 << 20
_TILE_MAX_COLUMNS = 32
_FILL_THREADS = 4


def _tile_shape(rows: int, columns: int) -> tuple[int, int]:
    """``(tile_rows, tile_columns)`` for a ``(rows, columns)`` float64
    matrix, from the shape alone: a narrow matrix is filled in row
    tiles that span every column, a wide one in groups of
    ``_TILE_MAX_COLUMNS`` columns with as many rows as the byte budget
    leaves."""
    tile_columns = max(1, min(columns, _TILE_MAX_COLUMNS))
    tile_rows = max(1, min(rows, _TILE_BYTES // (8 * tile_columns)))
    return tile_rows, tile_columns


def _fill(sources: list[np.ndarray], rows: int) -> np.ndarray:
    """The C-ordered float64 ``(rows, len(sources))`` matrix whose
    column ``j`` is ``sources[j]``, written one tile at a time: each
    source is read in contiguous runs into a small staging block, and
    the block is written out transposed, so no store crosses the whole
    matrix at a stride of a row. A matrix of many row tiles is shared
    out among ``_FILL_THREADS`` threads by rows: most of what is left
    of the time is the first touch of the matrix's fresh pages, which
    threads take side by side."""
    matrix = np.empty((rows, len(sources)), dtype=np.float64)
    tile_rows, tile_columns = _tile_shape(*matrix.shape)

    def fill_rows(first_row: int, last_row: int) -> None:
        staging = np.empty((tile_columns, tile_rows), dtype=np.float64)
        for start in range(first_row, last_row, tile_rows):
            stop = min(last_row, start + tile_rows)
            for first in range(0, len(sources), tile_columns):
                group = sources[first:first + tile_columns]
                block = staging[:len(group), :stop - start]
                for i, source in enumerate(group):
                    block[i] = source[start:stop]
                matrix[start:stop, first:first + len(group)] = block.T

    tiles = -(-rows // tile_rows)
    threads = min(_FILL_THREADS, tiles // _FILL_THREADS)  # several tiles each
    if threads <= 1:
        fill_rows(0, rows)
        return matrix
    share = -(-tiles // threads) * tile_rows
    with ThreadPoolExecutor(threads) as pool:
        shares = [
            pool.submit(fill_rows, first_row, min(rows, first_row + share))
            for first_row in range(0, rows, share)
        ]
        for done in shares:
            done.result()
    return matrix


class VectorAssembler:
    """Assembles numeric columns into one 2-D ``outputCol`` matrix — the
    bridge from the host dataframe to the device design matrix. A
    frame's assembly is remembered on the frame, which is sound because
    frames are immutable: the same assembler settings on the same frame
    return the same result frame."""

    def __init__(
        self,
        inputCols: Optional[list[str]] = None,
        outputCol: str = "features",
        handleInvalid: str = ERROR,
    ):
        self.inputCols = list(inputCols or [])
        self.outputCol = outputCol
        self.handleInvalid = handleInvalid

    def setHandleInvalid(self, value: str) -> "VectorAssembler":
        if value not in (ERROR, SKIP, KEEP):
            raise ValueError(f"invalid handleInvalid {value!r}")
        self.handleInvalid = value
        return self

    def transform(self, df: DataFrame) -> DataFrame:
        with _tracing.span("frame:assemble"):
            memo = df.__dict__.setdefault("_assembled", {})
            key = (tuple(self.inputCols), self.outputCol, self.handleInvalid)
            out = memo.get(key)
            passes = 0
            if out is None:
                out = memo[key] = self._transform(df)
                passes = 1
            matrix = out._column(self.outputCol)
            tile_rows, tile_columns = _tile_shape(*matrix.shape)
            _tracing.annotate(
                rows=matrix.shape[0],
                features=matrix.shape[1],
                bytes=int(matrix.nbytes),
                passes=passes,
                tile_rows=tile_rows,
                tile_columns=tile_columns,
            )
            return out

    def _transform(self, df: DataFrame) -> DataFrame:
        sources = []
        for name in self.inputCols:
            column = df._column(name)
            if column.ndim == 2:
                sources.extend(column.T)
            elif column.dtype == object:
                nulls = _is_null_array(column)
                sources.append(
                    np.array(
                        [
                            np.nan if null else float(v)
                            for v, null in zip(column, nulls)
                        ],
                        dtype=np.float64,
                    )
                )
            else:
                sources.append(column)
        with_nan = [source for source in sources if np.isnan(source).any()]
        if with_nan and self.handleInvalid == ERROR:
            raise ValueError(
                "VectorAssembler: null/NaN in input columns "
                "(handleInvalid='error')"
            )
        if with_nan and self.handleInvalid == SKIP:
            keep = np.ones(df.count(), dtype=bool)
            for source in with_nan:
                keep &= ~np.isnan(source)
            df = df._take(keep)
            sources = [source[keep] for source in sources]
        return df.withColumn(self.outputCol, _fill(sources, df.count()))


class Pipeline:
    """Minimal stage chainer (fit/transform protocol)."""

    def __init__(self, stages: Optional[list] = None):
        self.stages = list(stages or [])

    def fit(self, df: DataFrame) -> "PipelineModel":
        fitted = []
        current = df
        for stage in self.stages:
            if hasattr(stage, "fit"):
                model = stage.fit(current)
                current = model.transform(current)
                fitted.append(model)
            else:
                current = stage.transform(current)
                fitted.append(stage)
        return PipelineModel(fitted)


class PipelineModel:
    def __init__(self, stages: list):
        self.stages = stages

    def transform(self, df: DataFrame) -> DataFrame:
        for stage in self.stages:
            df = stage.transform(df)
        return df
