"""DataFrame facade: expressions, verbs, feature stages, and the full
documented preprocessor example running verbatim."""

import numpy as np
import pytest

from learningorchestra_tpu.core.ingest import ingest_csv, write_ingest_metadata
from learningorchestra_tpu.core.table import ColumnTable
from learningorchestra_tpu.frame import (
    DataFrame,
    StringIndexer,
    VectorAssembler,
    col,
    regexp_extract,
    when,
)
from learningorchestra_tpu.frame.pyspark_compat import run_preprocessor
from learningorchestra_tpu.ops.dtype import convert_field_types


@pytest.fixture()
def df():
    return DataFrame.from_table(
        ColumnTable.from_lists(
            {
                "name": ["Braund, Mr. Owen", "Cumings, Mrs. John", None],
                "age": [22.0, None, 26.0],
                "fare": [7.25, 71.28, 7.92],
            }
        )
    )


class TestExpressions:
    def test_arithmetic(self, df):
        out = df.withColumn("double_fare", col("fare") * 2 + 1)
        np.testing.assert_allclose(
            out._column("double_fare"), [15.5, 143.56, 16.84]
        )

    def test_when_isnull_otherwise(self, df):
        out = df.withColumn(
            "age", when(df["age"].isNull(), 99).otherwise(df["age"])
        )
        np.testing.assert_allclose(out._column("age"), [22, 99, 26])

    def test_equality_with_null_is_false(self, df):
        out = df.withColumn("is_b", when(df["name"] == "Braund, Mr. Owen", 1).otherwise(0))
        np.testing.assert_allclose(out._column("is_b"), [1, 0, 0])

    def test_regexp_extract(self, df):
        out = df.withColumn(
            "title", regexp_extract(col("name"), r"([A-Za-z]+)\.", 1)
        )
        assert list(out._column("title")) == ["Mr", "Mrs", None]

    def test_compound_condition(self, df):
        out = df.withColumn(
            "flag",
            when((df["fare"] > 7) & (df["age"].isNull()), 1).otherwise(0),
        )
        np.testing.assert_allclose(out._column("flag"), [0, 1, 0])


class TestVerbs:
    def test_rename_drop_columns(self, df):
        out = df.withColumnRenamed("fare", "price").drop("name")
        assert out.columns == ["age", "price"]

    def test_na_fill_dict(self, df):
        out = df.na.fill({"age": 0, "name": "unknown"})
        assert out._column("age")[1] == 0
        assert out._column("name")[2] == "unknown"

    def test_replace_list(self, df):
        out = df.replace(["Braund, Mr. Owen"], ["X"])
        assert out._column("name")[0] == "X"

    def test_random_split_deterministic(self, df):
        big = DataFrame({"x": np.arange(1000, dtype=np.float64)})
        a1, b1 = big.randomSplit([0.8, 0.2], seed=33)
        a2, b2 = big.randomSplit([0.8, 0.2], seed=33)
        assert a1.count() == a2.count() and b1.count() == b2.count()
        assert a1.count() + b1.count() == 1000
        assert abs(a1.count() - 800) < 60

    def test_first_and_schema(self, df):
        row = df.first()
        assert row["name"] == "Braund, Mr. Owen"
        assert row["age"] == 22.0
        assert df.schema.names == ["name", "age", "fare"]


class TestFeatureStages:
    def test_string_indexer_frequency_desc(self):
        df = DataFrame.from_table(
            ColumnTable.from_lists({"c": ["b", "a", "b", "c", "b", "a"]})
        )
        model = StringIndexer(inputCol="c", outputCol="c_index").fit(df)
        assert model.labels == ["b", "a", "c"]
        out = model.transform(df)
        np.testing.assert_allclose(out._column("c_index"), [0, 1, 0, 2, 0, 1])

    def test_string_indexer_unseen_errors(self):
        df = DataFrame.from_table(ColumnTable.from_lists({"c": ["a", "b"]}))
        model = StringIndexer(inputCol="c").fit(df)
        other = DataFrame.from_table(ColumnTable.from_lists({"c": ["z"]}))
        with pytest.raises(ValueError):
            model.transform(other)

    def test_vector_assembler_skip(self, df):
        assembler = VectorAssembler(
            inputCols=["age", "fare"], outputCol="features"
        ).setHandleInvalid("skip")
        out = assembler.transform(df)
        assert out.count() == 2  # the null-age row was skipped
        assert out.feature_matrix().shape == (2, 2)

    def test_vector_assembler_error(self, df):
        assembler = VectorAssembler(inputCols=["age"], outputCol="features")
        with pytest.raises(ValueError):
            assembler.transform(df)


def _reference_assembly(columns):
    """The plain reference of ISSUE 33: every input a float64 column
    block, the blocks joined side by side."""
    blocks = []
    for column in columns:
        if column.dtype == object:
            column = np.array(
                [np.nan if v is None else float(v) for v in column]
            )
        if column.ndim == 1:
            column = column.astype(np.float64)[:, None]
        blocks.append(column)
    return np.concatenate(blocks, axis=1)


def _numeric_columns(rows, columns, seed=0, read_only=False):
    table = np.random.default_rng(seed).standard_normal((columns, rows))
    if read_only:
        # as Column.to_float64 hands a column over: a contiguous view
        # of a buffer it does not own, not writeable
        table.flags.writeable = False
    return {f"c{j}": table[j] for j in range(columns)}


def _object_columns():
    return {
        "a": np.array([1.0, 2.0, 3.0, 4.0]),
        "o": np.array(["1.5", None, "3", "4e2"], dtype=object),
        "b": np.array([5.0, 6.0, 7.0, 8.0]),
    }


def _block_columns():
    rng = np.random.default_rng(3)
    return {
        "a": rng.standard_normal(7),
        "block": rng.standard_normal((7, 3)),
        "b": rng.standard_normal(7),
    }


def _nan_columns():
    columns = _numeric_columns(11, 4, seed=5)
    columns["c2"] = columns["c2"].copy()
    columns["c2"][[3, 8]] = np.nan
    return columns


# name: (the input columns, handleInvalid)
ASSEMBLY_CASES = {
    "1x1": (lambda: _numeric_columns(1, 1), "error"),
    "5x3": (lambda: _numeric_columns(5, 3), "error"),
    "tall_200003x28": (lambda: _numeric_columns(200_003, 28), "error"),
    "wide_257x2000": (lambda: _numeric_columns(257, 2000), "error"),
    "read_only_views": (
        lambda: _numeric_columns(5_000, 7, read_only=True),
        "error",
    ),
    "object_none_and_numeric_strings": (_object_columns, "keep"),
    "block_between_vectors": (_block_columns, "error"),
    "nan_keep": (_nan_columns, "keep"),
    "nan_skip": (_nan_columns, "skip"),
}


class TestAssemblyFill:
    """The tiled fill against the plain reference, bit for bit."""

    @pytest.mark.parametrize("case", list(ASSEMBLY_CASES))
    def test_matrix_is_the_reference_matrix(self, case):
        make, handle_invalid = ASSEMBLY_CASES[case]
        columns = make()
        expected = _reference_assembly(columns.values())
        rows = len(expected)
        kept = np.ones(rows, dtype=bool)
        if handle_invalid == "skip":
            kept = ~np.isnan(expected).any(axis=1)
            assert 0 < kept.sum() < rows
        frame = DataFrame({"row": np.arange(rows, dtype=np.float64), **columns})
        out = VectorAssembler(
            inputCols=list(columns), handleInvalid=handle_invalid
        ).transform(frame)
        matrix = out.feature_matrix()
        assert matrix.dtype == np.float64
        assert matrix.flags.c_contiguous
        assert matrix.shape == expected[kept].shape
        assert np.array_equal(matrix, expected[kept], equal_nan=True)
        # the frame's other columns are taken alike
        assert np.array_equal(out._column("row"), np.flatnonzero(kept))
        for name, column in columns.items():
            taken = out._column(name)
            if column.dtype == object:
                assert taken.tolist() == column[kept].tolist()
            else:
                assert np.array_equal(taken, column[kept], equal_nan=True)

    def test_zero_input_columns(self):
        frame = DataFrame({"row": np.arange(3.0)})
        matrix = VectorAssembler(inputCols=[]).transform(frame).feature_matrix()
        assert matrix.dtype == np.float64 and matrix.shape == (3, 0)

    def test_nan_under_error_raises_the_same_message(self):
        frame = DataFrame(_nan_columns())
        with pytest.raises(ValueError) as raised:
            VectorAssembler(inputCols=list(frame.columns)).transform(frame)
        assert str(raised.value) == (
            "VectorAssembler: null/NaN in input columns "
            "(handleInvalid='error')"
        )

    def test_tile_follows_the_shape(self):
        from learningorchestra_tpu.frame.feature import _tile_shape

        # few long columns: row tiles across every column; the tall
        # case above takes several and a ragged last one
        tile_rows, tile_columns = _tile_shape(8_388_608, 28)
        assert tile_columns == 28 and 1_024 <= tile_rows <= 8_192
        assert 200_003 > 3 * tile_rows and 200_003 % tile_rows
        # many columns: groups of them; the wide case above takes
        # several and a ragged last one
        tile_rows, tile_columns = _tile_shape(163_840, 2_000)
        assert tile_rows * tile_columns * 8 <= 1 << 20
        assert 2_000 > 3 * tile_columns and 2_000 % tile_columns
        assert _tile_shape(257, 2_000) == (257, tile_columns)
        assert _tile_shape(300, 4) == (300, 4)  # a small frame: one tile
        assert _tile_shape(0, 0) == (1, 1)


def _assemble_spans(trace):
    return [
        s["meta"] for s in trace.as_dict()["spans"] if s["name"] == "frame:assemble"
    ]


class TestAssemblyMemo:
    """A frame's assembly is remembered on the frame (frames are
    immutable), under the assembler's settings."""

    def _frame(self):
        return DataFrame(_nan_columns())

    def test_same_settings_same_frame_answer_from_the_memo(self):
        from learningorchestra_tpu.telemetry import tracing

        frame = self._frame()
        trace = tracing.Trace(name="memo")
        with tracing.activate(trace):
            first = VectorAssembler(inputCols=["c0", "c1"]).transform(frame)
            second = VectorAssembler(inputCols=["c0", "c1"]).transform(frame)
        assert second is first
        one, two = _assemble_spans(trace)
        assert (one["passes"], two["passes"]) == (1, 0)
        for meta in (one, two):
            assert (meta["rows"], meta["features"], meta["bytes"]) == (11, 2, 176)
            assert (meta["tile_rows"], meta["tile_columns"]) == (11, 2)

    @pytest.mark.parametrize(
        "other",
        ["inputCols", "outputCol", "handleInvalid", "frame"],
    )
    def test_anything_else_fills_anew(self, other):
        from learningorchestra_tpu.telemetry import tracing

        frame = self._frame()
        settings = dict(
            inputCols=["c0", "c2"], outputCol="features", handleInvalid="keep"
        )
        changed = dict(settings)
        target = frame
        if other == "inputCols":
            changed["inputCols"] = ["c2", "c0"]
        elif other == "outputCol":
            changed["outputCol"] = "vector"
        elif other == "handleInvalid":
            changed["handleInvalid"] = "skip"
        else:
            target = self._frame()
        trace = tracing.Trace(name="anew")
        with tracing.activate(trace):
            first = VectorAssembler(**settings).transform(frame)
            second = VectorAssembler(**changed).transform(target)
        assert second is not first
        assert [meta["passes"] for meta in _assemble_spans(trace)] == [1, 1]
        assert second.count() == (9 if other == "handleInvalid" else 11)

    def test_documented_preprocessor_evaluates_on_the_test_frame_itself(
        self, monkeypatch
    ):
        from learningorchestra_tpu.ml.builder import _alias_if_equal

        code = (
            "from pyspark.ml.feature import VectorAssembler\n"
            "feature_cols = [c for c in training_df.schema.names if c != 'label']\n"
            "assembler = VectorAssembler(inputCols=feature_cols, outputCol='features')\n"
            "features_training = assembler.transform(training_df)\n"
            "features_testing = assembler.transform(testing_df)\n"
            "features_evaluation = assembler.transform(testing_df)\n"
        )
        training = DataFrame(
            {"label": np.array([0.0, 1.0, 1.0]), **_numeric_columns(3, 2)}
        )
        testing = DataFrame(
            {"label": np.array([1.0, 0.0]), **_numeric_columns(2, 2, seed=1)}
        )
        out = run_preprocessor(code, training, testing)
        assert out["features_evaluation"] is out["features_testing"]
        assert out["features_training"] is not out["features_testing"]

        def unread(self, features_col="features"):
            raise AssertionError("the identity test must answer first")

        monkeypatch.setattr(DataFrame, "feature_matrix", unread)
        assert (
            _alias_if_equal(out["features_evaluation"], out["features_testing"])
            is out["features_testing"]
        )


# The documented preprocessor example, verbatim from the reference's
# docs/model_builder.md (the compatibility contract for user code).
DOCUMENTED_PREPROCESSOR = r"""
from pyspark.ml import Pipeline
from pyspark.sql.functions import (
    mean, col, split,
    regexp_extract, when, lit)

from pyspark.ml.feature import (
    VectorAssembler,
    StringIndexer
)

TRAINING_DF_INDEX = 0
TESTING_DF_INDEX = 1

training_df = training_df.withColumnRenamed('Survived', 'label')
testing_df = testing_df.withColumn('label', lit(0))
datasets_list = [training_df, testing_df]

for index, dataset in enumerate(datasets_list):
    dataset = dataset.withColumn(
        "Initial",
        regexp_extract(col("Name"), "([A-Za-z]+)\.", 1))
    datasets_list[index] = dataset

misspelled_initials = [
    'Mlle', 'Mme', 'Ms', 'Dr',
    'Major', 'Lady', 'Countess',
    'Jonkheer', 'Col', 'Rev',
    'Capt', 'Sir', 'Don'
]
correct_initials = [
    'Miss', 'Miss', 'Miss', 'Mr',
    'Mr', 'Mrs', 'Mrs',
    'Other', 'Other', 'Other',
    'Mr', 'Mr', 'Mr'
]
for index, dataset in enumerate(datasets_list):
    dataset = dataset.replace(misspelled_initials, correct_initials)
    datasets_list[index] = dataset

initials_age = {"Miss": 22,
                "Other": 46,
                "Master": 5,
                "Mr": 33,
                "Mrs": 36}
for index, dataset in enumerate(datasets_list):
    for initial, initial_age in initials_age.items():
        dataset = dataset.withColumn(
            "Age",
            when((dataset["Initial"] == initial) &
                 (dataset["Age"].isNull()), initial_age).otherwise(
                    dataset["Age"]))
        datasets_list[index] = dataset

for index, dataset in enumerate(datasets_list):
    dataset = dataset.na.fill({"Embarked": 'S'})
    datasets_list[index] = dataset

for index, dataset in enumerate(datasets_list):
    dataset = dataset.withColumn("Family_Size", col('SibSp')+col('Parch'))
    dataset = dataset.withColumn('Alone', lit(0))
    dataset = dataset.withColumn(
        "Alone",
        when(dataset["Family_Size"] == 0, 1).otherwise(dataset["Alone"]))
    datasets_list[index] = dataset

text_fields = ["Sex", "Embarked", "Initial"]
for column in text_fields:
    for index, dataset in enumerate(datasets_list):
        dataset = StringIndexer(
            inputCol=column, outputCol=column+"_index").\
                fit(dataset).\
                transform(dataset)
        datasets_list[index] = dataset

non_required_columns = ["Name", "Embarked", "Sex", "Initial"]
for index, dataset in enumerate(datasets_list):
    dataset = dataset.drop(*non_required_columns)
    datasets_list[index] = dataset

training_df = datasets_list[TRAINING_DF_INDEX]
testing_df = datasets_list[TESTING_DF_INDEX]

assembler = VectorAssembler(
    inputCols=training_df.columns[:],
    outputCol="features")
assembler.setHandleInvalid('skip')

features_training = assembler.transform(training_df)
(features_training, features_evaluation) =\
    features_training.randomSplit([0.8, 0.2], seed=33)
features_testing = assembler.transform(testing_df)
"""


class TestDocumentedPreprocessor:
    def test_runs_verbatim(self, store, titanic_csv):
        write_ingest_metadata(store, "titanic", titanic_csv)
        ingest_csv(store, "titanic", titanic_csv)
        convert_field_types(
            store,
            "titanic",
            {
                f: "number"
                for f in ("PassengerId", "Survived", "Pclass", "Age", "SibSp", "Parch", "Fare")
            },
        )
        table = ColumnTable.from_store(store, "titanic")
        training_df = DataFrame.from_table(table)
        testing_df = DataFrame.from_table(table).drop("Survived")

        out = run_preprocessor(DOCUMENTED_PREPROCESSOR, training_df, testing_df)
        features_training = out["features_training"]
        features_testing = out["features_testing"]
        features_evaluation = out["features_evaluation"]

        assert "features" in features_training.columns
        assert "label" in features_training.columns
        n_train = features_training.count()
        n_eval = features_evaluation.count()
        assert n_train + n_eval == 8  # no rows lost: Age was imputed
        assert features_testing.count() == 8
        # assembled width: label,PassengerId,Pclass,Age,SibSp,Parch,Fare,
        # Family_Size,Alone,Sex_index,Embarked_index,Initial_index
        assert features_training.feature_matrix().shape[1] == 12
        # label round-trips for training
        labels = features_training.label_vector()
        assert set(labels) <= {0, 1}


class TestReviewRegressions:
    def test_ne_null_is_false(self, df):
        out = df.filter(df["name"] != "Braund, Mr. Owen")
        assert list(out._column("name")) == ["Cumings, Mrs. John"]

    def test_na_fill_scalar_type_matching(self, df):
        filled = df.na.fill("S")  # string fill skips numeric columns
        assert np.isnan(filled._column("age")[1])
        assert filled._column("name")[2] == "S"
        filled = df.na.fill(0)  # numeric fill skips string columns
        assert filled._column("age")[1] == 0
        assert filled._column("name")[2] is None

    def test_when_without_otherwise_numeric_nan(self, df):
        out = df.withColumn("flag", when(df["fare"] > 7.5, 1))
        flag = out._column("flag")
        assert flag.dtype == np.float64
        assert np.isnan(flag[0]) and flag[1] == 1
        bumped = out.withColumn("flag2", col("flag") + 1)
        assert bumped._column("flag2")[1] == 2

    def test_label_vector_rejects_nan(self, df):
        frame = df.withColumnRenamed("age", "label")
        with pytest.raises(ValueError):
            frame.label_vector()

    def test_split_equal_lengths_stays_1d(self, df):
        from learningorchestra_tpu.frame.expressions import split

        frame = DataFrame.from_table(
            ColumnTable.from_lists({"s": ["a b", "c d", "e f"]})
        )
        out = frame.withColumn("parts", split(col("s"), " "))
        parts = out._column("parts")
        assert parts.ndim == 1 and parts[0] == ["a", "b"]

    def test_reflected_div_and_neg(self, df):
        out = df.withColumn("inv", 1 / col("fare")).withColumn(
            "neg", -col("fare")
        )
        np.testing.assert_allclose(out._column("inv")[0], 1 / 7.25)
        np.testing.assert_allclose(out._column("neg")[0], -7.25)
