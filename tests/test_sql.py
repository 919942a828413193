"""DataFrame engine tests (the Spark-substrate analog — SURVEY.md §7)."""

import numpy as np
import pytest

from sparkdl_tpu.sql import Row, TPUSession, col, lit, udf
from sparkdl_tpu.sql.functions import pandas_udf, struct


@pytest.fixture()
def df(tpu_session):
    data = [(i, f"name_{i}", float(i) * 1.5) for i in range(10)]
    return tpu_session.createDataFrame(data, ["id", "name", "score"])


def test_create_collect_count(df):
    assert df.count() == 10
    rows = df.collect()
    assert rows[0] == Row(id=0, name="name_0", score=0.0)
    assert rows[3].name == "name_3"
    assert rows[3]["score"] == 4.5
    assert df.columns == ["id", "name", "score"]


def test_partitioning(tpu_session):
    df = tpu_session.createDataFrame([(i,) for i in range(100)], ["x"], numPartitions=7)
    assert df.getNumPartitions() == 7
    assert df.count() == 100
    assert df.repartition(3).getNumPartitions() == 3
    assert sorted(r.x for r in df.repartition(3).collect()) == list(range(100))


def test_select_and_exprs(df):
    out = df.select("id", (col("score") * 2).alias("double_score"))
    rows = out.collect()
    assert out.columns == ["id", "double_score"]
    assert rows[2].double_score == 6.0


def test_with_column_and_udf(df):
    plus = udf(lambda a, b: a + b)
    out = df.withColumn("total", plus(col("id"), col("score")))
    assert out.collect()[4].total == 4 + 6.0
    # engine extension: plain callable rowwise
    out2 = df.withColumn("name_len", lambda s: len(s), "name")
    assert out2.collect()[0].name_len == 6


def test_vectorized_udf(df):
    doubler = pandas_udf(lambda xs: [x * 2 for x in xs])
    out = df.select(doubler(col("id")).alias("d"))
    assert [r.d for r in out.collect()] == [2 * i for i in range(10)]


def test_filter_where_limit(df):
    assert df.filter(col("id") >= 5).count() == 5
    assert df.where(lambda r: r.id % 2 == 0).count() == 5
    assert df.limit(3).count() == 3


def test_random_split(tpu_session):
    df = tpu_session.createDataFrame([(i,) for i in range(200)], ["x"])
    a, b = df.randomSplit([0.7, 0.3], seed=42)
    assert a.count() + b.count() == 200
    assert 100 < a.count() < 180


def test_map_partitions(df):
    def fn(part):
        return {"sum": [sum(part["id"])]}

    out = df.repartition(2).mapPartitions(fn)
    assert sum(r.sum for r in out.collect()) == sum(range(10))


def test_map_all_partitions_sees_every_border_and_keeps_the_partitioning(df):
    seen = []

    def fn(parts):
        seen.append([list(p["id"]) for p in parts])
        # a stage may look across the border: each row gets the length of
        # the partition AFTER its own
        sizes = [len(p["id"]) for p in parts] + [0]
        return [{"id": p["id"], "next": [sizes[i + 1]] * len(p["id"])}
                for i, p in enumerate(parts)]

    parted = df.repartition(3)
    out = parted.mapAllPartitions(fn)
    assert seen == [[[0, 1, 2], [3, 4, 5], [6, 7, 8, 9]]]  # one call, eager
    assert [list(p["id"]) for p in out._partitions] == seen[0]
    assert [(r.id, r.next) for r in out.collect()] == [
        (i, 3 if i < 3 else 4 if i < 6 else 0) for i in range(10)]
    # and what mapPartitions gives, it gives
    per_partition = parted.mapPartitions(lambda p: {"id": p["id"]})
    together = parted.mapAllPartitions(
        lambda parts: [{"id": p["id"]} for p in parts])
    assert together.schema == per_partition.schema
    assert together.collect() == per_partition.collect()
    with pytest.raises(ValueError, match="3 partitions in, 1 out"):
        parted.mapAllPartitions(lambda parts: parts[:1])


def test_map_in_arrow(df):
    import pyarrow as pa

    def fn(batch):
        ids = batch.column(0)
        return pa.record_batch({"id2": pa.compute.multiply(ids, 2)})

    out = df.select("id").mapInArrow(fn)
    assert [r.id2 for r in out.collect()] == [2 * i for i in range(10)]


def test_struct_and_get_field(df):
    out = df.select(struct("id", "name").alias("s")).withColumn(
        "sid", col("s").getField("id")
    )
    assert out.collect()[7].sid == 7


def test_temp_view_and_sql(df, tpu_session):
    df.createOrReplaceTempView("people")
    tpu_session.udf.register("doubled", lambda x: x * 2)
    out = tpu_session.sql("SELECT doubled(score) AS ds, name FROM people WHERE id >= 8")
    rows = out.collect()
    assert len(rows) == 2
    assert rows[0].ds == 8 * 1.5 * 2
    out2 = tpu_session.sql("SELECT * FROM people LIMIT 4")
    assert out2.count() == 4 and out2.columns == ["id", "name", "score"]


def test_union_drop_rename(df):
    assert df.union(df).count() == 20
    assert df.drop("name").columns == ["id", "score"]
    assert df.withColumnRenamed("name", "label").columns == ["id", "label", "score"]


def test_numpy_column(tpu_session):
    arrs = [(i, np.full((3,), i, dtype=np.float32)) for i in range(6)]
    df = tpu_session.createDataFrame(arrs, ["i", "arr"])
    row = df.collect()[4]
    np.testing.assert_array_equal(row.arr, np.full((3,), 4, dtype=np.float32))


def test_to_pandas(df):
    pdf = df.toPandas()
    assert list(pdf.columns) == ["id", "name", "score"]
    assert len(pdf) == 10


def test_column_eq_returns_column_not_bool(df):
    """pyspark parity wart, pinned: Column.__eq__ builds an expression, so
    Columns are unhashable and `in` checks on Column lists are meaningless —
    use .alias()/_name comparisons instead."""
    c = col("id") == 3
    assert isinstance(c, type(col("id")))
    with pytest.raises(TypeError):
        hash(col("id"))


def test_schema_inference_skips_leading_nones(tpu_session):
    """Type inference probes for the first non-None value anywhere in the
    column (previously: first partition's first row only)."""
    from sparkdl_tpu.sql.types import infer_type

    df = tpu_session.createDataFrame(
        [(None,), (None,), (7,)], ["x"], numPartitions=2
    )
    out = df.select("x")
    want = type(infer_type(7))
    assert isinstance(out.schema["x"].dataType, want)

    out2 = df.withColumn("y", col("x") * 2)
    assert isinstance(out2.schema["y"].dataType, want)


class TestWherePredicates:
    """Compound WHERE parsing (AND/OR/NOT/IN/parens/IS NULL) — the subset of
    Catalyst's predicate surface the reference examples exercise."""

    @pytest.fixture()
    def view(self, tpu_session):
        data = [
            (i, f"name_{i}", float(i) * 1.5, i % 3 if i != 4 else None)
            for i in range(10)
        ]
        df = tpu_session.createDataFrame(
            data, ["id", "name", "score", "label"]
        )
        df.createOrReplaceTempView("preds")
        return tpu_session

    def _ids(self, session, where):
        out = session.sql(f"SELECT id FROM preds WHERE {where}")
        return sorted(r.id for r in out.collect())

    def test_and(self, view):
        assert self._ids(view, "id >= 3 AND id < 6") == [3, 4, 5]

    def test_or(self, view):
        assert self._ids(view, "id < 2 OR id > 8") == [0, 1, 9]

    def test_precedence_and_binds_tighter(self, view):
        # a OR b AND c  ==  a OR (b AND c)
        assert self._ids(view, "id = 9 OR id > 2 AND id < 5") == [3, 4, 9]

    def test_parens_override(self, view):
        assert self._ids(view, "(id = 9 OR id > 2) AND id < 5") == [3, 4]

    def test_in(self, view):
        assert self._ids(view, "id IN (1, 3, 5)") == [1, 3, 5]

    def test_in_strings(self, view):
        assert self._ids(view, "name IN ('name_2', 'name_7')") == [2, 7]

    def test_not_in(self, view):
        assert self._ids(view, "id NOT IN (0,1,2,3,4,5,6,7)") == [8, 9]

    def test_not(self, view):
        assert self._ids(view, "NOT id < 8") == [8, 9]

    def test_is_null(self, view):
        assert self._ids(view, "label IS NULL") == [4]
        assert self._ids(view, "label IS NOT NULL") == [
            0, 1, 2, 3, 5, 6, 7, 8, 9
        ]

    def test_verdict_example_shape(self, view):
        # the VERDICT r2 #8 acceptance query shape:
        #   SELECT udf(image) FROM t WHERE label IN (0,1) AND height > 100
        assert self._ids(view, "label IN (0, 1) AND score > 3") == [
            3, 6, 7, 9
        ]

    def test_float_and_negative_literals(self, view):
        assert self._ids(view, "score >= 10.5") == [7, 8, 9]
        assert self._ids(view, "id > -1 AND score < 1.0") == [0]

    def test_mixed_case_keywords(self, view):
        assert self._ids(view, "id in (1, 2) or id = 9") == [1, 2, 9]

    def test_isin_column_api(self, view):
        df = view.table("preds")
        out = df.filter(col("id").isin(2, 4, 6)).collect()
        assert sorted(r.id for r in out) == [2, 4, 6]
        out2 = df.filter(col("id").isin([7, 8])).collect()
        assert sorted(r.id for r in out2) == [7, 8]

    def test_unsupported_raises(self, view):
        with pytest.raises(ValueError):
            view.sql("SELECT id FROM preds WHERE id ~~ 3")
        with pytest.raises(ValueError):
            view.sql("SELECT id FROM preds WHERE id IN ()")
        with pytest.raises(ValueError):
            view.sql("SELECT id FROM preds WHERE (id = 1")

    def test_struct_field_reference(self, tpu_session):
        data = [
            (i, {"height": 10 * i, "width": 5}) for i in range(6)
        ]
        df = tpu_session.createDataFrame(data, ["id", "image"])
        df.createOrReplaceTempView("structs")
        out = tpu_session.sql(
            "SELECT id FROM structs WHERE image.height > 20 AND id IN (3, 4)"
        )
        assert sorted(r.id for r in out.collect()) == [3, 4]

    def test_null_three_valued_logic(self, tpu_session):
        """SQL 3VL (as in Spark/Catalyst): TRUE OR NULL = TRUE keeps the
        row; FALSE AND NULL = FALSE (not NULL)."""
        data = [(1, 0), (4, None), (9, 2)]
        df = tpu_session.createDataFrame(data, ["id", "lbl"])
        df.createOrReplaceTempView("nulls")
        out = tpu_session.sql("SELECT id FROM nulls WHERE lbl = 1 OR id = 4")
        assert sorted(r.id for r in out.collect()) == [4]
        # NULL AND TRUE = NULL; NOT NULL = NULL -> row 4 dropped (as Spark)
        out2 = tpu_session.sql(
            "SELECT id FROM nulls WHERE NOT (lbl = 1 AND id = 4)"
        )
        assert sorted(r.id for r in out2.collect()) == [1, 9]
        # NULL AND FALSE = FALSE; NOT FALSE = TRUE -> row 4 kept
        out3 = tpu_session.sql(
            "SELECT id FROM nulls WHERE NOT (lbl = 1 AND id = 5)"
        )
        assert sorted(r.id for r in out3.collect()) == [1, 4, 9]

    def test_leading_dot_float_literal(self, view):
        # regression: `score > .5` parsed before the tokenizer rewrite
        assert self._ids(view, "score > .5") == list(range(1, 10))


class TestGroupByAggregates:
    """GroupedData + the SQL GROUP BY / aggregate / ORDER BY surface."""

    @pytest.fixture()
    def gdf(self, tpu_session):
        data = [
            (i, i % 3, float(i), None if i == 4 else i * 2) for i in range(9)
        ]
        df = tpu_session.createDataFrame(
            data, ["id", "label", "score", "maybe"]
        )
        df.createOrReplaceTempView("agg_t")
        return df

    def test_grouped_data_api(self, gdf):
        out = gdf.groupBy("label").agg({"score": "avg", "*": "count"})
        rows = {r.label: r for r in out.collect()}
        assert rows[0]["count(*)"] == 3 and rows[0]["avg(score)"] == 3.0
        assert rows[1]["count(*)"] == 3 and rows[1]["avg(score)"] == 4.0

        counts = {r.label: r["count"] for r in gdf.groupBy("label").count().collect()}
        assert counts == {0: 3, 1: 3, 2: 3}

        sums = {r.label: r["sum(score)"] for r in gdf.groupBy("label").sum("score").collect()}
        assert sums == {0: 9.0, 1: 12.0, 2: 15.0}

    def test_null_excluded_from_aggregates(self, gdf):
        # id=4 (label 1) has maybe=None: COUNT(col) skips it, AVG ignores it
        out = {r.label: r for r in gdf.groupBy("label").agg(
            {"maybe": "count"}).collect()}
        assert out[1]["count(maybe)"] == 2
        avg = {r.label: r["avg(maybe)"] for r in gdf.groupBy("label").avg(
            "maybe").collect()}
        assert avg[1] == (1 * 2 + 7 * 2) / 2

    def test_sql_group_by(self, gdf, tpu_session):
        out = tpu_session.sql(
            "SELECT label, COUNT(*) AS n, AVG(score) AS m FROM agg_t "
            "WHERE id < 8 GROUP BY label ORDER BY label"
        ).collect()
        assert [r.label for r in out] == [0, 1, 2]
        assert [r.n for r in out] == [3, 3, 2]
        assert out[2].m == (2.0 + 5.0) / 2

    def test_sql_global_aggregate(self, gdf, tpu_session):
        (row,) = tpu_session.sql(
            "SELECT COUNT(*) AS n, MAX(score) AS mx FROM agg_t"
        ).collect()
        assert row.n == 9 and row.mx == 8.0

    def test_sql_order_by_desc_limit(self, gdf, tpu_session):
        out = tpu_session.sql(
            "SELECT id FROM agg_t ORDER BY id DESC LIMIT 3"
        ).collect()
        assert [r.id for r in out] == [8, 7, 6]

    def test_sql_rejects_bare_column_in_group_query(self, gdf, tpu_session):
        with pytest.raises(ValueError, match="GROUP BY key or an aggregate"):
            tpu_session.sql(
                "SELECT score, COUNT(*) FROM agg_t GROUP BY label"
            )

    def test_duplicate_aggregates_need_distinct_aliases(self, gdf, tpu_session):
        out = tpu_session.sql(
            "SELECT label, AVG(score) AS a, AVG(score) AS b FROM agg_t "
            "GROUP BY label ORDER BY label"
        ).collect()
        assert out[0].a == out[0].b == 3.0
        with pytest.raises(ValueError, match="duplicate output columns"):
            tpu_session.sql(
                "SELECT AVG(score), AVG(score) FROM agg_t GROUP BY label"
            )

    def test_global_aggregate_on_empty_view(self, tpu_session):
        df = tpu_session.createDataFrame([(1, 2.0)], ["id", "v"]).filter(
            lambda r: False
        )
        df.createOrReplaceTempView("empty_t")
        (row,) = tpu_session.sql(
            "SELECT COUNT(*) AS n, SUM(v) AS s FROM empty_t"
        ).collect()
        assert row.n == 0 and row.s is None

    def test_aggregate_unknown_column_raises(self, gdf, tpu_session):
        with pytest.raises(KeyError, match="nope"):
            tpu_session.sql("SELECT SUM(nope) FROM agg_t GROUP BY label")

    def test_order_by_non_projected_column(self, gdf, tpu_session):
        out = tpu_session.sql(
            "SELECT label FROM agg_t ORDER BY score DESC LIMIT 2"
        ).collect()
        assert [r.label for r in out] == [8 % 3, 7 % 3]
        with pytest.raises(ValueError, match="ORDER BY"):
            tpu_session.sql("SELECT label FROM agg_t ORDER BY nope")

    def test_scalar_udf_named_like_aggregate_wins_outside_group_by(
        self, gdf, tpu_session
    ):
        tpu_session.udf.register("min", lambda x: x * 10)
        try:
            out = tpu_session.sql(
                "SELECT min(score) AS m FROM agg_t LIMIT 3"
            )
            assert [r.m for r in out.collect()] == [0.0, 10.0, 20.0]
            # inside GROUP BY the call is ambiguous (SQL aggregate vs the
            # registered per-row UDF) — it used to silently resolve to the
            # aggregate; now it must refuse
            with pytest.raises(ValueError, match="ambiguous"):
                tpu_session.sql(
                    "SELECT MIN(score) AS m FROM agg_t "
                    "GROUP BY label ORDER BY m"
                )
        finally:
            del tpu_session.udf._udfs["min"]
        # with the UDF gone the aggregate resolves again
        out2 = tpu_session.sql(
            "SELECT MIN(score) AS m FROM agg_t GROUP BY label ORDER BY m"
        ).collect()
        assert [r.m for r in out2] == [0.0, 1.0, 2.0]

    def test_no_arg_sum_skips_non_numeric(self, tpu_session):
        df = tpu_session.createDataFrame(
            [(0, "a", 1.0), (0, "b", 2.0), (1, "c", 3.0)],
            ["k", "name", "v"],
        )
        out = {r.k: r["sum(v)"] for r in df.groupBy("k").sum().collect()}
        assert out == {0: 3.0, 1: 3.0}
        with pytest.raises(ValueError, match="sum\\(\\*\\) is not defined"):
            df.groupBy("k").agg({"*": "sum"})

    def test_having(self, gdf, tpu_session):
        out = tpu_session.sql(
            "SELECT label, COUNT(*) AS n, SUM(score) AS s FROM agg_t "
            "GROUP BY label HAVING s > 10 ORDER BY label"
        ).collect()
        assert [(r.label, r.s) for r in out] == [(1, 12.0), (2, 15.0)]
        with pytest.raises(ValueError, match="HAVING requires"):
            tpu_session.sql("SELECT id FROM agg_t HAVING id > 1")

    def test_having_on_non_projected_key_and_alias_hint(
        self, gdf, tpu_session
    ):
        # HAVING may reference a group key the projection drops
        out = tpu_session.sql(
            "SELECT SUM(score) AS s FROM agg_t GROUP BY label "
            "HAVING label > 0 ORDER BY s"
        ).collect()
        assert [r.s for r in out] == [12.0, 15.0]
        # direct aggregate calls in HAVING compute as hidden columns
        # (they used to require an AS alias)
        rows = tpu_session.sql(
            "SELECT label, COUNT(*) AS n FROM agg_t GROUP BY label "
            "HAVING count(*) > 1"
        ).collect()
        assert all(r.n > 1 for r in rows) and len(rows) >= 1
        assert rows and "__having_0" not in rows[0]._fields

    def test_having_unknown_column_gets_hint(self, gdf, tpu_session):
        with pytest.raises(ValueError, match="HAVING.*AS"):
            tpu_session.sql(
                "SELECT label, SUM(score) AS s FROM agg_t GROUP BY label "
                "HAVING cnt > 1"
            )


class TestJoins:
    """DataFrame.join + SQL JOIN...ON (the reference delegated joins to
    Spark SQL/Catalyst — SURVEY.md §1 L0, §3.3; semantics pinned here
    follow documented Spark behavior: USING-form key dedup with keys
    first, NULL keys never match, outer variants keep unmatched rows)."""

    @pytest.fixture()
    def preds(self, tpu_session):
        df = tpu_session.createDataFrame(
            [(1, 0.9, "cat"), (2, 0.4, "dog"), (3, 0.7, "cat"),
             (None, 0.5, "bird")],
            ["img_id", "score", "pred"],
        )
        df.createOrReplaceTempView("preds")
        return df

    @pytest.fixture()
    def labels(self, tpu_session):
        df = tpu_session.createDataFrame(
            [(1, "cat"), (2, "cat"), (4, "dog"), (None, "fish")],
            ["img_id", "truth"],
        )
        df.createOrReplaceTempView("labels")
        return df

    # -- DataFrame API ---------------------------------------------------
    def test_inner_join_dedupes_key_keys_first(self, preds, labels):
        out = preds.join(labels, on="img_id")
        assert out.columns == ["img_id", "score", "pred", "truth"]
        rows = sorted(out.collect(), key=lambda r: r.img_id)
        assert [(r.img_id, r.pred, r.truth) for r in rows] == [
            (1, "cat", "cat"), (2, "dog", "cat")
        ]

    def test_null_keys_never_match(self, preds, labels):
        # both sides have an img_id=None row; SQL equality on NULL is
        # not true, so no combined row may appear
        out = preds.join(labels, on="img_id")
        assert all(r.img_id is not None for r in out.collect())

    def test_left_outer_keeps_unmatched_and_null_keys(self, preds, labels):
        out = preds.join(labels, on="img_id", how="left")
        rows = out.collect()
        assert len(rows) == 4  # every preds row survives
        by_pred = {r.pred: r for r in rows}
        assert by_pred["cat"].truth in ("cat", None)  # img 1 or 3
        assert by_pred["bird"].img_id is None and by_pred["bird"].truth is None
        unmatched = [r for r in rows if r.truth is None]
        assert {r.score for r in unmatched} == {0.7, 0.5}

    def test_right_and_full_outer(self, preds, labels):
        right = preds.join(labels, on="img_id", how="right_outer")
        rrows = right.collect()
        assert len(rrows) == 4  # every labels row survives
        assert {r.truth for r in rrows} == {"cat", "dog", "fish"}
        # img_id=4 has no pred: left columns null, key from the right
        lbl4 = next(r for r in rrows if r.img_id == 4)
        assert lbl4.score is None and lbl4.pred is None

        full = preds.join(labels, on="img_id", how="outer")
        # 2 matches + 2 left-only (3, None) + 2 right-only (4, None)
        assert len(full.collect()) == 6

    def test_pair_keys_keep_both_columns(self, tpu_session, preds):
        meta = tpu_session.createDataFrame(
            [(1, "s3://a"), (3, "s3://b")], ["image", "origin"]
        )
        out = preds.join(meta, on=[("img_id", "image")])
        assert out.columns == ["img_id", "score", "pred", "image", "origin"]
        rows = sorted(out.collect(), key=lambda r: r.img_id)
        assert [(r.img_id, r.image, r.origin) for r in rows] == [
            (1, 1, "s3://a"), (3, 3, "s3://b")
        ]

    def test_duplicate_rows_multiply(self, tpu_session):
        a = tpu_session.createDataFrame([(1, "x"), (1, "y")], ["k", "a"])
        b = tpu_session.createDataFrame([(1, "p"), (1, "q")], ["k", "b"])
        out = a.join(b, on="k")
        assert len(out.collect()) == 4  # cross product within the key

    def test_join_errors(self, preds, labels, tpu_session):
        with pytest.raises(KeyError, match="join key 'nope'"):
            preds.join(labels, on="nope")
        with pytest.raises(ValueError, match="Unsupported join type"):
            preds.join(labels, on="img_id", how="sideways")
        # non-key name collision ('pred' vs a second 'pred') errors with
        # the offending names instead of silently shadowing
        dup = tpu_session.createDataFrame(
            [(1, "cat")], ["img_id", "pred"]
        )
        with pytest.raises(ValueError, match=r"duplicate column names \['pred'\]"):
            preds.join(dup, on="img_id")

    def test_join_partitioned_inputs(self, tpu_session):
        n = 100
        a = tpu_session.createDataFrame(
            [(i, i * 2) for i in range(n)], ["k", "a"], numPartitions=7
        )
        b = tpu_session.createDataFrame(
            [(i, i * 3) for i in range(0, n, 2)], ["k", "b"],
            numPartitions=3,
        )
        out = a.join(b, on="k")
        rows = sorted(out.collect(), key=lambda r: r.k)
        assert len(rows) == 50
        assert all(r.a == r.k * 2 and r.b == r.k * 3 for r in rows)
        assert out.getNumPartitions() == 7  # bucketed by the wider side

    # -- SQL dialect -----------------------------------------------------
    def test_sql_inner_join(self, preds, labels, tpu_session):
        out = tpu_session.sql(
            "SELECT img_id, pred, truth FROM preds "
            "JOIN labels ON preds.img_id = labels.img_id"
        )
        rows = sorted(out.collect(), key=lambda r: r.img_id)
        assert [(r.img_id, r.pred, r.truth) for r in rows] == [
            (1, "cat", "cat"), (2, "dog", "cat")
        ]

    def test_sql_left_join_with_where(self, preds, labels, tpu_session):
        out = tpu_session.sql(
            "SELECT img_id, score, truth FROM preds "
            "LEFT OUTER JOIN labels ON preds.img_id = labels.img_id "
            "WHERE truth IS NULL"
        )
        assert {r.score for r in out.collect()} == {0.7, 0.5}

    def test_sql_join_aliases(self, preds, labels, tpu_session):
        out = tpu_session.sql(
            "SELECT img_id, pred, truth FROM preds p "
            "JOIN labels l ON p.img_id = l.img_id"
        )
        assert len(out.collect()) == 2

    def test_sql_join_group_by(self, preds, labels, tpu_session):
        # accuracy-style analytics over the joined result
        out = tpu_session.sql(
            "SELECT truth, COUNT(*) AS n, AVG(score) AS mean_score "
            "FROM preds JOIN labels ON preds.img_id = labels.img_id "
            "GROUP BY truth HAVING n >= 1 ORDER BY truth"
        )
        rows = out.collect()
        assert [(r.truth, r.n) for r in rows] == [("cat", 2)]
        assert rows[0].mean_score == pytest.approx((0.9 + 0.4) / 2)

    def test_sql_three_table_chain(self, preds, labels, tpu_session):
        tpu_session.createDataFrame(
            [("cat", 1), ("dog", 2)], ["truth", "species_id"]
        ).createOrReplaceTempView("species")
        out = tpu_session.sql(
            "SELECT img_id, species_id FROM preds "
            "JOIN labels ON preds.img_id = labels.img_id "
            "JOIN species ON labels.truth = species.truth"
        )
        rows = sorted(out.collect(), key=lambda r: r.img_id)
        assert [(r.img_id, r.species_id) for r in rows] == [(1, 1), (2, 1)]

    def test_sql_self_join_with_aliases(self, preds, tpu_session):
        # aliases hide the table name (Spark semantics), so self-joins
        # with distinct aliases resolve; same-named NON-key columns
        # still collide by design (the engine's duplicate-name error),
        # so a same-table self-join keys on every shared column
        out = tpu_session.sql(
            "SELECT pred FROM preds a JOIN preds b ON a.img_id = b.img_id "
            "AND a.score = b.score AND a.pred = b.pred"
        )
        assert len(out.collect()) == 3  # 1, 2, 3 match themselves

    def test_mixed_on_list(self, tpu_session, preds):
        meta = tpu_session.createDataFrame(
            [(1, "cat", "s3://a")], ["image", "pred", "origin"]
        )
        out = preds.join(meta, on=["pred", ("img_id", "image")])
        rows = out.collect()
        assert out.columns == [
            "pred", "img_id", "score", "image", "origin"
        ]
        assert [(r.img_id, r.pred) for r in rows] == [(1, "cat")]
        with pytest.raises(ValueError, match="join key entry"):
            preds.join(meta, on=[("img_id", "image", "extra")])
        with pytest.raises(ValueError, match="Unsupported JOIN condition"):
            tpu_session.sql(
                "SELECT img_id FROM preds JOIN labels ON img_id = img_id"
            )
        with pytest.raises(ValueError, match="one side must reference"):
            tpu_session.sql(
                "SELECT img_id FROM preds "
                "JOIN labels ON mystery.img_id = labels.img_id"
            )
        with pytest.raises(ValueError, match="distinct aliases"):
            tpu_session.sql(
                "SELECT img_id FROM preds "
                "JOIN preds ON preds.img_id = preds.img_id"
            )

    def test_sql_without_join_still_parses(self, preds, tpu_session):
        # the FROM-alias and joins extensions must not disturb plain
        # queries (regression: alias regex could swallow WHERE)
        out = tpu_session.sql(
            "SELECT img_id FROM preds WHERE score > 0.5 ORDER BY img_id"
        )
        assert [r.img_id for r in out.collect()] == [1, 3]


class TestSqlExpressions:
    """Arithmetic projections/aggregate args, COUNT(DISTINCT),
    LIKE/BETWEEN (VERDICT r3 #5 — the reference had all of Spark SQL's
    expression surface; these are the reconstructed high-traffic parts)."""

    @pytest.fixture()
    def edf(self, tpu_session):
        df = tpu_session.createDataFrame(
            [("a.png", "s3", 0.2, 1), ("b.png", "s3", 0.4, 1),
             ("c.jpg", "web", 0.6, 2), ("d.jpg", "web", 0.8, 2),
             ("e.png", "web", None, 2), (None, "s3", 0.5, 3)],
            ["origin", "source", "score", "label"],
        )
        df.createOrReplaceTempView("expr_t")
        return df

    def test_arithmetic_projection(self, edf, tpu_session):
        out = tpu_session.sql(
            "SELECT origin, score * 100 AS pct, (score + 1) / 2 AS half "
            "FROM expr_t WHERE score IS NOT NULL"
        ).collect()
        assert out[0].pct == pytest.approx(20.0)
        assert out[0].half == pytest.approx(0.6)
        # NULL propagates through arithmetic
        all_rows = tpu_session.sql(
            "SELECT score * 100 AS pct FROM expr_t"
        ).collect()
        assert any(r.pct is None for r in all_rows)

    def test_default_expression_column_name(self, edf, tpu_session):
        out = tpu_session.sql("SELECT score * 100 FROM expr_t")
        assert out.columns == ["score * 100"]

    def test_arithmetic_in_where(self, edf, tpu_session):
        out = tpu_session.sql(
            "SELECT origin FROM expr_t WHERE score * 100 > 45"
        ).collect()
        assert {r.origin for r in out} == {"c.jpg", "d.jpg", None}

    def test_unary_minus_and_precedence(self, edf, tpu_session):
        out = tpu_session.sql(
            "SELECT origin FROM expr_t WHERE -score + 1 > 0.7"
        ).collect()  # 1 - score > 0.7 => score < 0.3
        assert {r.origin for r in out} == {"a.png"}
        rows = tpu_session.sql(
            "SELECT 2 + 3 * 4 AS v FROM expr_t LIMIT 1"
        ).collect()
        assert rows[0].v == 14  # * binds tighter than +

    def test_like(self, edf, tpu_session):
        out = tpu_session.sql(
            "SELECT origin FROM expr_t WHERE origin LIKE '%.png'"
        ).collect()
        assert {r.origin for r in out} == {"a.png", "b.png", "e.png"}
        # NULL LIKE -> NULL -> filtered out (3VL); NOT LIKE keeps jpgs
        out2 = tpu_session.sql(
            "SELECT origin FROM expr_t WHERE origin NOT LIKE '%.png'"
        ).collect()
        assert {r.origin for r in out2} == {"c.jpg", "d.jpg"}
        # _ matches exactly one character
        out3 = tpu_session.sql(
            "SELECT origin FROM expr_t WHERE origin LIKE '_.png'"
        ).collect()
        assert {r.origin for r in out3} == {"a.png", "b.png", "e.png"}

    def test_between(self, edf, tpu_session):
        out = tpu_session.sql(
            "SELECT origin FROM expr_t WHERE score BETWEEN 0.4 AND 0.6"
        ).collect()
        assert {r.origin for r in out} == {"b.png", "c.jpg", None}
        out2 = tpu_session.sql(
            "SELECT origin FROM expr_t "
            "WHERE score NOT BETWEEN 0.4 AND 0.6 AND score IS NOT NULL"
        ).collect()
        assert {r.origin for r in out2} == {"a.png", "d.jpg"}

    def test_count_distinct(self, edf, tpu_session):
        rows = tpu_session.sql(
            "SELECT label, COUNT(DISTINCT source) AS ns FROM expr_t "
            "GROUP BY label ORDER BY label"
        ).collect()
        assert [(r.label, r.ns) for r in rows] == [(1, 1), (2, 1), (3, 1)]
        total = tpu_session.sql(
            "SELECT COUNT(DISTINCT source) AS ns FROM expr_t"
        ).collect()
        assert total[0].ns == 2
        with pytest.raises(ValueError, match="DISTINCT is supported"):
            tpu_session.sql(
                "SELECT SUM(DISTINCT score) FROM expr_t GROUP BY label"
            )

    def test_aggregate_over_expression(self, edf, tpu_session):
        rows = tpu_session.sql(
            "SELECT label, AVG(score * 100) AS pct FROM expr_t "
            "WHERE score IS NOT NULL GROUP BY label ORDER BY label"
        ).collect()
        assert rows[0].pct == pytest.approx(30.0)  # (20+40)/2
        assert rows[1].pct == pytest.approx(70.0)  # (60+80)/2
        # derived argument columns never leak into the output
        assert not any(c.startswith("__agg_arg") for c in
                       tpu_session.sql(
                           "SELECT AVG(score * 100) AS pct FROM expr_t "
                           "GROUP BY label"
                       ).columns)

    def test_verdict_acceptance_query(self, edf, tpu_session):
        # the VERDICT r3 "done" shape: expression aggregate + HAVING with
        # a direct COUNT(DISTINCT ...) call
        rows = tpu_session.sql(
            "SELECT label, AVG(score * 100) AS pct FROM expr_t "
            "WHERE score IS NOT NULL "
            "GROUP BY label HAVING COUNT(DISTINCT origin) > 1 "
            "ORDER BY label"
        ).collect()
        assert [(r.label, round(r.pct, 6)) for r in rows] == [
            (1, 30.0), (2, 70.0)
        ]

    def test_udf_in_expression(self, edf, tpu_session):
        tpu_session.udf.register("twice", lambda v: None if v is None
                                 else v * 2)
        rows = tpu_session.sql(
            "SELECT twice(score) + 1 AS t FROM expr_t "
            "WHERE score IS NOT NULL ORDER BY t"
        ).collect()
        assert rows[0].t == pytest.approx(1.4)

    def test_aggregate_inside_expression_rejected(self, edf, tpu_session):
        with pytest.raises(ValueError, match="cannot appear inside"):
            tpu_session.sql("SELECT avg(score) + 1 FROM expr_t")


class TestSqlResolution:
    """Qualifier resolution, ORDER BY alias precedence, and parser
    robustness on malformed input."""

    @pytest.fixture()
    def views(self, tpu_session):
        tpu_session.createDataFrame(
            [(1, 0.9), (2, 0.4), (3, 0.7)], ["img_id", "score"]
        ).createOrReplaceTempView("t")
        tpu_session.createDataFrame(
            [(1, "cat"), (2, "dog")], ["img_id", "meta"]
        ).createOrReplaceTempView("m")
        return tpu_session

    def test_qualified_refs_after_join(self, views):
        # the natural Spark form: qualified columns in WHERE and the
        # projection resolve against the joined (flat) columns
        rows = views.sql(
            "SELECT t.score, m.meta FROM t JOIN m ON t.img_id = m.img_id "
            "WHERE t.score > 0.5"
        ).collect()
        assert [(r.score, r.meta) for r in rows] == [(0.9, "cat")]

    def test_qualified_refs_single_table(self, views):
        rows = views.sql(
            "SELECT t.img_id FROM t WHERE t.score >= 0.7 ORDER BY img_id"
        ).collect()
        assert [r.img_id for r in rows] == [1, 3]

    def test_order_by_alias_shadows_input_column(self, views):
        # SQL resolution: a select-list alias wins over a same-named
        # input column — sort by the NEGATED value here
        rows = views.sql(
            "SELECT img_id, -score AS score FROM t ORDER BY score"
        ).collect()
        assert [r.img_id for r in rows] == [1, 3, 2]  # -0.9 < -0.7 < -0.4

    def test_struct_column_named_like_view_keeps_field_access(
        self, tpu_session
    ):
        # a view named like one of its struct columns: column resolution
        # wins over the table qualifier, so image.height stays a
        # struct-field access (regression guard for the qualifier
        # feature)
        tpu_session.createDataFrame(
            [{"image": {"height": 120, "width": 60}, "label": 1},
             {"image": {"height": 40, "width": 20}, "label": 0}]
        ).createOrReplaceTempView("image")
        rows = tpu_session.sql(
            "SELECT label FROM image WHERE image.height > 100"
        ).collect()
        assert [r.label for r in rows] == [1]
        # same resolution inside aggregate arguments and HAVING
        agg = tpu_session.sql(
            "SELECT label, MAX(image.height) AS h FROM image "
            "GROUP BY label HAVING MAX(image.height) > 10 ORDER BY label"
        ).collect()
        assert [(r.label, r.h) for r in agg] == [(0, 40), (1, 120)]

    def test_malformed_join_query_fails_fast(self, views):
        import time

        bad = (
            "SELECT x FROM t "
            + "JOIN m ON t.img_id = m.img_id " * 24
            + "WHERE ??? BROKEN"
        )
        t0 = time.perf_counter()
        with pytest.raises((ValueError, KeyError)):
            views.sql(bad)
        assert time.perf_counter() - t0 < 1.0, "regex backtracking blowup"


class TestDistinctNaOrder:
    """distinct/dropDuplicates, df.na drop/fill, multi-key ORDER BY —
    the high-traffic pyspark surface around the serving-analytics flow."""

    @pytest.fixture()
    def ddf(self, tpu_session):
        return tpu_session.createDataFrame(
            [(1, "a", 0.5), (1, "a", 0.5), (2, "a", None),
             (2, "b", 0.7), (None, "b", 0.7)],
            ["k", "tag", "score"],
        )

    def test_distinct_and_drop_duplicates(self, ddf, tpu_session):
        assert ddf.distinct().count() == 4  # exact dup row collapses
        # subset form keeps the FIRST row per key
        firsts = ddf.dropDuplicates(["tag"]).collect()
        assert [(r.k, r.tag) for r in firsts] == [(1, "a"), (2, "b")]
        with pytest.raises(KeyError):
            ddf.dropDuplicates(["nope"])
        ddf.createOrReplaceTempView("ddup")
        rows = tpu_session.sql("SELECT DISTINCT tag FROM ddup").collect()
        assert sorted(r.tag for r in rows) == ["a", "b"]
        rows2 = tpu_session.sql(
            "SELECT DISTINCT k, tag FROM ddup WHERE k IS NOT NULL"
        ).collect()
        assert len(rows2) == 3

    def test_na_drop(self, ddf):
        assert ddf.na.drop().count() == 3  # rows with any null dropped
        assert ddf.dropna(how="all").count() == 5
        assert ddf.na.drop(subset=["score"]).count() == 4
        assert ddf.na.drop(thresh=3).count() == 3
        with pytest.raises(ValueError, match="how"):
            ddf.na.drop(how="some")

    def test_na_fill(self, ddf):
        # scalar fill touches only type-compatible columns (Spark rule)
        filled = ddf.na.fill(0.0)
        rows = filled.collect()
        assert all(r.score is not None for r in rows)
        assert any(r.k is None for r in rows) is False  # int col filled too
        # strings untouched by numeric fill
        strs = ddf.na.fill("x").collect()
        assert any(r.score is None for r in strs)  # floats untouched
        # dict form
        d = ddf.fillna({"score": -1.0}).collect()
        assert sorted(r.score for r in d)[0] == -1.0

    def test_multi_key_order_by(self, ddf, tpu_session):
        out = ddf.orderBy("tag", "score", ascending=[True, False])
        rows = out.collect()
        assert [(r.tag, r.score) for r in rows] == [
            ("a", 0.5), ("a", 0.5), ("a", None),  # desc: nulls last
            ("b", 0.7), ("b", 0.7),
        ]
        # SQL form with per-key direction
        ddf.createOrReplaceTempView("ord_t")
        got = tpu_session.sql(
            "SELECT k, tag, score FROM ord_t "
            "ORDER BY tag ASC, score DESC"
        ).collect()
        assert [(r.tag, r.score) for r in got] == [
            ("a", 0.5), ("a", 0.5), ("a", None),
            ("b", 0.7), ("b", 0.7),
        ]

    def test_order_by_null_ordering(self, tpu_session):
        df = tpu_session.createDataFrame(
            [(3,), (None,), (1,)], ["v"]
        )
        asc = [r.v for r in df.orderBy("v").collect()]
        assert asc == [None, 1, 3]  # Spark: NULLS FIRST ascending
        desc = [r.v for r in df.orderBy("v", ascending=False).collect()]
        assert desc == [3, 1, None]  # NULLS LAST descending

    def test_order_by_mixed_alias_and_hidden_input(self, tpu_session):
        tpu_session.createDataFrame(
            [(1, 0.5, "b"), (2, 0.5, "a"), (3, 0.9, "c")],
            ["k", "score", "tag"],
        ).createOrReplaceTempView("mix_t")
        # 'score' is an alias shadowing an input column (negated), 'tag'
        # is an unprojected input column — per-key resolution: alias
        # value sorts, tag rides along hidden and is dropped after
        rows = tpu_session.sql(
            "SELECT k, -score AS score FROM mix_t ORDER BY score, tag"
        ).collect()
        assert [r.k for r in rows] == [3, 2, 1]  # -0.9 < -0.5(a) < -0.5(b)
        assert rows and rows[0]._fields == ("k", "score")
        # alias-only multi-key still valid
        rows2 = tpu_session.sql(
            "SELECT score AS s, k FROM mix_t ORDER BY s, k"
        ).collect()
        assert [r.k for r in rows2] == [1, 2, 3]
        with pytest.raises(ValueError, match="select list"):
            tpu_session.sql(
                "SELECT DISTINCT k FROM mix_t ORDER BY k, tag"
            )

    def test_drop_duplicates_array_cells_full_content(self, tpu_session):
        # large arrays must fingerprint by content, not truncated repr
        a = np.zeros(2048, np.float32)
        b = np.zeros(2048, np.float32)
        b[500] = 1.0  # differs only in the repr-elided middle
        df = tpu_session.createDataFrame(
            [(1, a), (2, b), (3, a.copy())], ["k", "feat"]
        )
        out = df.distinct().collect()
        assert len(out) == 3  # k differs everywhere
        out2 = df.dropDuplicates(["feat"]).collect()
        assert [r.k for r in out2] == [1, 2]  # a == a.copy(), b distinct

    def test_na_fill_casts_to_column_type(self, tpu_session):
        df = tpu_session.createDataFrame(
            [(1, 1.5), (None, None)], ["i", "f"]
        )
        rows = df.na.fill(0.5).collect()
        filled_i = [r.i for r in rows if r.i is not None]
        assert 0 in filled_i and all(isinstance(v, int) for v in filled_i)
        assert any(r.f == 0.5 for r in rows)

    def test_distinct_order_by_unselected_always_rejected(self, tpu_session):
        tpu_session.createDataFrame(
            [(1, "a")], ["k", "tag"]
        ).createOrReplaceTempView("dgd_t")
        with pytest.raises(ValueError, match="select list"):
            tpu_session.sql("SELECT DISTINCT k FROM dgd_t ORDER BY tag")

    def test_na_fill_ignores_incompatible_columns(self, tpu_session):
        df = tpu_session.createDataFrame(
            [(1, None, None), (None, "y", 0.5)], ["i", "s", "f"]
        )
        # string fill into an int column via subset: ignored, not a crash
        rows = df.na.fill("unknown", subset=["i", "s"]).collect()
        assert any(r.i is None for r in rows)  # int column untouched
        assert all(r.s is not None for r in rows)
        # dict form likewise ignores the type mismatch
        rows2 = df.fillna({"i": "x", "f": 1}).collect()
        assert any(r.i is None for r in rows2)
        assert all(isinstance(r.f, float) for r in rows2 if r.f is not None)


class TestCaseCastBuiltins:
    """CASE WHEN / CAST / builtin scalar functions — the Spark SQL
    expression idioms serving analytics lean on (AVG(CASE WHEN ...) is
    the canonical accuracy query)."""

    @pytest.fixture()
    def cdf(self, tpu_session):
        tpu_session.createDataFrame(
            [("a.png", "cat", "cat", 0.91), ("b.png", "dog", "cat", 0.44),
             ("c.png", "cat", "cat", 0.67), ("d.png", None, "dog", None)],
            ["origin", "pred", "truth", "score"],
        ).createOrReplaceTempView("case_t")
        return tpu_session

    def test_case_when_projection(self, cdf):
        rows = cdf.sql(
            "SELECT origin, CASE WHEN pred = truth THEN 'hit' "
            "WHEN pred IS NULL THEN 'missing' ELSE 'miss' END AS outcome "
            "FROM case_t ORDER BY origin"
        ).collect()
        assert [r.outcome for r in rows] == [
            "hit", "miss", "hit", "missing"
        ]

    def test_accuracy_idiom(self, cdf):
        # the classic: per-class accuracy via AVG(CASE WHEN ...)
        rows = cdf.sql(
            "SELECT truth, AVG(CASE WHEN pred = truth THEN 1.0 "
            "ELSE 0.0 END) AS acc FROM case_t GROUP BY truth "
            "ORDER BY truth"
        ).collect()
        assert [(r.truth, round(r.acc, 4)) for r in rows] == [
            ("cat", round(2 / 3, 4)), ("dog", 0.0)
        ]

    def test_case_without_else_yields_null(self, cdf):
        rows = cdf.sql(
            "SELECT CASE WHEN score > 0.9 THEN 'high' END AS band "
            "FROM case_t"
        ).collect()
        assert sorted(str(r.band) for r in rows) == [
            "None", "None", "None", "high"
        ]

    def test_cast(self, cdf):
        rows = cdf.sql(
            "SELECT origin, CAST(score * 100 AS int) AS pct FROM case_t "
            "WHERE score IS NOT NULL ORDER BY origin"
        ).collect()
        assert [r.pct for r in rows] == [91, 44, 67]
        assert all(isinstance(r.pct, int) for r in rows)
        with pytest.raises(ValueError, match="CAST target"):
            cdf.sql("SELECT CAST(score AS blob) FROM case_t")

    def test_builtins(self, cdf):
        rows = cdf.sql(
            "SELECT UPPER(pred) AS up, LENGTH(origin) AS n, "
            "ROUND(score * 100) AS r, COALESCE(score, -1.0) AS s, "
            "ABS(-2) AS a FROM case_t ORDER BY origin"
        ).collect()
        assert rows[0].up == "CAT" and rows[0].n == 5
        assert rows[0].r == 91 and rows[0].a == 2
        # NULL propagation vs COALESCE
        assert rows[3].up is None and rows[3].s == -1.0
        with pytest.raises(KeyError, match="Undefined function"):
            cdf.sql("SELECT frobnicate(score) FROM case_t")
        # a registered UDF shadows a builtin of the same name
        cdf.udf.register("upper", lambda v: "udf!")
        got = cdf.sql("SELECT upper(pred) AS u FROM case_t LIMIT 1").collect()
        assert got[0].u == "udf!"

    def test_null_literal(self, cdf):
        rows = cdf.sql(
            "SELECT COALESCE(NULL, pred) AS p FROM case_t ORDER BY origin"
        ).collect()
        assert rows[0].p == "cat"

    def test_case_conditional_evaluation(self, tpu_session):
        # the SQL guarantee: guarded branches never evaluate on rows
        # their condition excludes (guard-then-divide must not crash)
        tpu_session.createDataFrame(
            [(100, 4), (50, 0), (30, 3)], ["total", "n"]
        ).createOrReplaceTempView("guard_t")
        rows = tpu_session.sql(
            "SELECT CASE WHEN n != 0 THEN total / n ELSE -1 END AS avg_v "
            "FROM guard_t"
        ).collect()
        assert [r.avg_v for r in rows] == [25.0, -1, 10.0]

    def test_cast_invalid_yields_null(self, tpu_session):
        tpu_session.createDataFrame(
            [("12",), ("x",), (None,), ("3.7",)], ["s"]
        ).createOrReplaceTempView("cast_t")
        rows = tpu_session.sql(
            "SELECT CAST(s AS int) AS i FROM cast_t"
        ).collect()
        assert [r.i for r in rows] == [12, None, None, 3]
        bools = tpu_session.sql(
            "SELECT CAST(s AS boolean) AS b FROM cast_t"
        ).collect()
        assert [b.b for b in bools] == [None, None, None, None]

    def test_round_half_up_and_null_digits(self, tpu_session):
        tpu_session.createDataFrame(
            [(2.5, 0), (3.5, 0), (2.345, 2), (1.0, None)],
            ["v", "d"],
        ).createOrReplaceTempView("round_t")
        rows = tpu_session.sql(
            "SELECT ROUND(v, d) AS r FROM round_t"
        ).collect()
        assert rows[0].r == 3 and rows[1].r == 4  # HALF_UP, not banker's
        assert rows[2].r == pytest.approx(2.35)
        assert rows[3].r is None  # NULL digits propagate

    def test_udf_precedence_case_insensitive(self, tpu_session):
        tpu_session.createDataFrame(
            [("a",)], ["k"]
        ).createOrReplaceTempView("ci_t")
        tpu_session.udf.register("upper", lambda v: "udf!")
        for spelling in ("upper", "UPPER", "Upper"):
            got = tpu_session.sql(
                f"SELECT {spelling}(k) AS u FROM ci_t"
            ).collect()
            assert got[0].u == "udf!", spelling


class TestAdviceR4Fixes:
    """Regression tests for the round-4 advisor findings (ADVICE.md)."""

    def test_divide_by_zero_yields_null(self, tpu_session):
        tpu_session.createDataFrame(
            [(10.0, 2.0), (5.0, 0.0), (None, 3.0)], ["a", "b"]
        ).createOrReplaceTempView("dz_t")
        rows = tpu_session.sql("SELECT a / b AS q FROM dz_t").collect()
        assert rows[0].q == 5.0
        assert rows[1].q is None  # Spark: x / 0 is NULL, not a crash
        assert rows[2].q is None

    def test_like_backslash_escapes(self, tpu_session):
        tpu_session.createDataFrame(
            [("100%",), ("100x",), ("a_b",), ("axb",)], ["s"]
        ).createOrReplaceTempView("lk_t")
        rows = tpu_session.sql(
            r"SELECT s FROM lk_t WHERE s LIKE '100\%'"
        ).collect()
        assert [r.s for r in rows] == ["100%"]
        rows = tpu_session.sql(
            r"SELECT s FROM lk_t WHERE s LIKE 'a\_b'"
        ).collect()
        assert [r.s for r in rows] == ["a_b"]
        # unescaped wildcards still behave
        assert tpu_session.sql(
            "SELECT s FROM lk_t WHERE s LIKE '100_'"
        ).count() == 2

    def test_udf_case_ambiguity_raises(self, tpu_session):
        tpu_session.udf.register("myFn", lambda v: 1)
        tpu_session.udf.register("MYFN", lambda v: 2)
        # exact spellings still resolve
        assert tpu_session.udf.resolve("myFn") is not None
        assert tpu_session.udf.resolve("MYFN") is not None
        with pytest.raises(KeyError, match="[Aa]mbiguous"):
            tpu_session.udf.resolve("myfn")

    def test_drop_duplicates_mixed_type_dict_keys(self, tpu_session):
        d1 = {1: "a", "x": "b"}  # int and str keys: bare sorted() raises
        d2 = {"x": "b", 1: "a"}  # same content, different insertion order
        d3 = {1: "a", "x": "c"}
        df = tpu_session.createDataFrame(
            [(1, d1), (2, d2), (3, d3)], ["id", "meta"]
        )
        out = df.dropDuplicates(["meta"])
        assert sorted(r.id for r in out.collect()) == [1, 3]

    def test_divide_by_zero_numpy_scalar_yields_null(self, tpu_session):
        a = np.float64(5.0)
        z = np.float64(0.0)
        tpu_session.createDataFrame(
            [(a, z), (a, np.float64(2.0))], ["x", "y"]
        ).createOrReplaceTempView("npz_t")
        rows = tpu_session.sql("SELECT x / y AS q FROM npz_t").collect()
        assert rows[0].q is None  # numpy would give inf, not raise
        assert rows[1].q == 2.5

    def test_udf_ambiguous_membership_keeps_bool_contract(self, tpu_session):
        tpu_session.udf.register("ambFn", lambda v: 1)
        tpu_session.udf.register("AMBFN", lambda v: 2)
        assert "ambfn" in tpu_session.udf  # no KeyError out of `in`

    def test_drop_duplicates_numeric_key_spellings(self, tpu_session):
        # {1: 'a', 2.0: 'b'} == {1: 'a', 2: 'b'} as Python dicts — one
        # fingerprint, one surviving row
        df = tpu_session.createDataFrame(
            [(1, {1: "a", 2.0: "b"}), (2, {1: "a", 2: "b"})], ["id", "meta"]
        )
        assert [r.id for r in df.dropDuplicates(["meta"]).collect()] == [1]


class _PoisonColumn(list):
    """A column whose DATA cannot be touched: any element access or
    iteration raises.  len() stays legal (partition row counts are
    metadata, not data)."""

    def __getitem__(self, i):
        raise AssertionError("poisoned column was materialized")

    def __iter__(self):
        raise AssertionError("poisoned column was iterated")


class TestAggregationPushdown:
    """Partial aggregation + projection pushdown (VERDICT r4 item 2)."""

    def test_group_by_never_touches_unreferenced_columns(self, tpu_session):
        df = tpu_session.createDataFrame(
            [(i % 3, float(i), b"imgbytes") for i in range(12)],
            ["label", "score", "image"],
            numPartitions=3,
        )
        for part in df._partitions:
            part["image"] = _PoisonColumn(part["image"])
        out = df.groupBy("label").agg({"score": "avg", "*": "count"})
        got = {r.label: (r["avg(score)"], r["count(*)"]) for r in out.collect()}
        assert got == {0: (4.5, 4), 1: (5.5, 4), 2: (6.5, 4)}

    def test_sql_group_by_never_touches_unreferenced_columns(
        self, tpu_session
    ):
        df = tpu_session.createDataFrame(
            [(i % 2, float(i), b"imgbytes") for i in range(8)],
            ["label", "score", "image"],
            numPartitions=2,
        )
        for part in df._partitions:
            part["image"] = _PoisonColumn(part["image"])
        df.createOrReplaceTempView("poisoned")
        rows = tpu_session.sql(
            "SELECT label, SUM(score) AS s FROM poisoned GROUP BY label"
        ).collect()
        assert {r.label: r.s for r in rows} == {0: 12.0, 1: 16.0}

    def test_partials_merge_across_partitions(self, tpu_session):
        # values deliberately split so no single partition sees the full
        # group; the merged result must equal the global aggregate
        vals = [float(v) for v in (5, 1, 9, 2, 8, 3, 7, 4, 6, 0)]
        df = tpu_session.createDataFrame(
            [(v,) for v in vals], ["x"], numPartitions=5
        )
        row = df.groupBy().agg(
            {"x": "avg"}
        ).collect()[0]
        assert row["avg(x)"] == pytest.approx(np.mean(vals))
        row = df.groupBy().agg({"x": "stddev"}).collect()[0]
        assert row["stddev(x)"] == pytest.approx(np.std(vals, ddof=1))

    def test_order_by_preserves_partitioning(self, tpu_session):
        df = tpu_session.createDataFrame(
            [(i * 7 % 10, i) for i in range(10)], ["k", "v"],
            numPartitions=4,
        )
        out = df.orderBy("k")
        assert out.getNumPartitions() == 4
        assert [r.k for r in out.collect()] == sorted(r.k for r in df.collect())
        # a downstream mapPartitions still sees 4 partitions of data
        seen = []
        out.foreachPartition(lambda p: seen.append(len(p["k"])))
        assert len(seen) == 4 and sum(seen) == 10


class TestNewAggregates:
    """stddev/variance/collect_* (VERDICT r4 item 6) + output typing
    (item 8)."""

    @pytest.fixture()
    def adf(self, tpu_session):
        data = [
            ("a", 1.0), ("a", 2.0), ("a", 4.0),
            ("b", 10.0), ("b", None),
        ]
        df = tpu_session.createDataFrame(data, ["k", "x"], numPartitions=3)
        df.createOrReplaceTempView("agg_t")
        return df

    def test_stddev_variance_vs_numpy(self, tpu_session, adf):
        a = np.array([1.0, 2.0, 4.0])
        rows = tpu_session.sql(
            "SELECT k, STDDEV(x) AS sd, VARIANCE(x) AS vr, "
            "STDDEV_POP(x) AS sdp, VAR_POP(x) AS vrp "
            "FROM agg_t GROUP BY k ORDER BY k"
        ).collect()
        ra = rows[0]
        assert ra.sd == pytest.approx(np.std(a, ddof=1))
        assert ra.vr == pytest.approx(np.var(a, ddof=1))
        assert ra.sdp == pytest.approx(np.std(a))
        assert ra.vrp == pytest.approx(np.var(a))
        rb = rows[1]  # single non-null value: sample estimator is NaN
        assert np.isnan(rb.sd) and np.isnan(rb.vr)
        assert rb.sdp == 0.0 and rb.vrp == 0.0

    def test_stddev_of_no_rows_is_null(self, tpu_session):
        tpu_session.createDataFrame(
            [(1.0,)], ["x"]
        ).createOrReplaceTempView("empty_src")
        row = tpu_session.sql(
            "SELECT STDDEV(x) AS sd FROM empty_src WHERE x > 99"
        ).collect()[0]
        assert row.sd is None

    def test_collect_list_and_set(self, tpu_session, adf):
        rows = tpu_session.sql(
            "SELECT k, COLLECT_LIST(x) AS xs FROM agg_t GROUP BY k "
            "ORDER BY k"
        ).collect()
        assert rows[0].xs == [1.0, 2.0, 4.0]
        assert rows[1].xs == [10.0]  # NULL excluded, as Spark
        df2 = tpu_session.createDataFrame(
            [("a", 1), ("a", 1), ("a", 2)], ["k", "v"]
        )
        out = df2.groupBy("k").agg({"v": "collect_set"})
        assert sorted(out.collect()[0]["collect_set(v)"]) == [1, 2]

    def test_collect_list_schema_is_array(self, tpu_session, adf):
        from sparkdl_tpu.sql.types import ArrayType, DoubleType

        out = adf.groupBy("k").agg({"x": "collect_list"})
        assert out.schema["collect_list(x)"].dataType == ArrayType(DoubleType())

    def test_aggregate_schema_from_declared_types(self, tpu_session):
        from sparkdl_tpu.sql.types import (
            DoubleType, LongType, StringType,
        )

        df = tpu_session.createDataFrame(
            [("a", 2, 1.5, "s")], ["k", "i", "f", "s"]
        )
        out = df.groupBy("k").agg(
            {"i": "sum", "f": "avg", "s": "min", "*": "count"}
        )
        assert out.schema["k"].dataType == StringType()
        assert out.schema["sum(i)"].dataType == LongType()
        assert out.schema["avg(f)"].dataType == DoubleType()
        assert out.schema["min(s)"].dataType == StringType()
        assert out.schema["count(*)"].dataType == LongType()

    def test_all_null_aggregate_column_keeps_type_and_fills(
        self, tpu_session
    ):
        from sparkdl_tpu.sql.types import DoubleType

        # a full-outer join whose right side never matches: every
        # right-origin value is NULL, but the declared type must survive
        # aggregation so fillna(0) still applies (VERDICT r4 weak #4)
        left = tpu_session.createDataFrame(
            [("a", 1.0), ("b", 2.0)], ["k", "x"]
        )
        right = tpu_session.createDataFrame(
            [("z", 9.5)], ["k", "y"]
        )
        joined = left.join(right, "k", how="full")
        agg = joined.groupBy("k").agg({"y": "max"})
        f = agg.schema["max(y)"]
        assert f.dataType == DoubleType()
        filled = agg.na.fill(0.0)
        vals = {r.k: r["max(y)"] for r in filled.collect()}
        assert vals["a"] == 0.0 and vals["b"] == 0.0 and vals["z"] == 9.5


class TestWindowFunctions:
    """ROW_NUMBER/RANK/DENSE_RANK OVER (VERDICT r4 item 1)."""

    @pytest.fixture()
    def scored(self, tpu_session):
        tpu_session.createDataFrame(
            [
                ("cat", "a.png", 0.9), ("cat", "b.png", 0.7),
                ("cat", "c.png", 0.9), ("dog", "d.png", 0.6),
                ("dog", "e.png", 0.95), ("dog", "f.png", 0.6),
            ],
            ["label", "origin", "score"], numPartitions=3,
        ).createOrReplaceTempView("win_t")

    def test_row_number_partitioned_desc(self, tpu_session, scored):
        rows = tpu_session.sql(
            "SELECT origin, ROW_NUMBER() OVER "
            "(PARTITION BY label ORDER BY score DESC) AS rn FROM win_t"
        ).collect()
        got = {r.origin: r.rn for r in rows}
        # ties broken by input order (deterministic): a before c
        assert got == {
            "a.png": 1, "c.png": 2, "b.png": 3,
            "e.png": 1, "d.png": 2, "f.png": 3,
        }

    def test_rank_vs_dense_rank_ties(self, tpu_session, scored):
        rows = tpu_session.sql(
            "SELECT origin, RANK() OVER (PARTITION BY label ORDER BY "
            "score DESC) AS rk, DENSE_RANK() OVER (PARTITION BY label "
            "ORDER BY score DESC) AS dr FROM win_t"
        ).collect()
        got = {r.origin: (r.rk, r.dr) for r in rows}
        assert got["a.png"] == (1, 1) and got["c.png"] == (1, 1)
        assert got["b.png"] == (3, 2)  # RANK gaps, DENSE_RANK doesn't
        assert got["d.png"] == (2, 2) and got["f.png"] == (2, 2)
        assert got["e.png"] == (1, 1)

    def test_window_no_partition(self, tpu_session, scored):
        rows = tpu_session.sql(
            "SELECT origin, ROW_NUMBER() OVER (ORDER BY score) AS rn "
            "FROM win_t WHERE label = 'dog'"
        ).collect()
        assert {r.origin: r.rn for r in rows} == {
            "d.png": 1, "f.png": 2, "e.png": 3,
        }

    def test_window_with_where_and_limit(self, tpu_session, scored):
        rows = tpu_session.sql(
            "SELECT origin, ROW_NUMBER() OVER (ORDER BY score DESC) AS rn "
            "FROM win_t WHERE label = 'cat' ORDER BY rn LIMIT 2"
        ).collect()
        # WHERE narrows BEFORE the window numbers rows (SQL order)
        assert [(r.origin, r.rn) for r in rows] == [
            ("a.png", 1), ("c.png", 2),
        ]

    def test_star_plus_window(self, tpu_session, scored):
        out = tpu_session.sql(
            "SELECT *, RANK() OVER (ORDER BY score DESC) AS rk FROM win_t"
        )
        assert out.columns == ["label", "origin", "score", "rk"]
        assert out.count() == 6

    def test_window_preserves_partitioning(self, tpu_session, scored):
        out = tpu_session.sql(
            "SELECT *, ROW_NUMBER() OVER (PARTITION BY label ORDER BY "
            "score) AS rn FROM win_t"
        )
        assert out.getNumPartitions() == 3

    def test_windowed_subquery_topk_per_label(self, tpu_session, scored):
        rows = tpu_session.sql(
            "SELECT label, origin FROM (SELECT label, origin, "
            "ROW_NUMBER() OVER (PARTITION BY label ORDER BY score DESC) "
            "AS rn FROM win_t) t WHERE t.rn <= 2 ORDER BY label, origin"
        ).collect()
        assert [(r.label, r.origin) for r in rows] == [
            ("cat", "a.png"), ("cat", "c.png"),
            ("dog", "d.png"), ("dog", "e.png"),
        ]

    def test_unsupported_window_fn_errors(self, tpu_session, scored):
        with pytest.raises(ValueError, match="window"):
            tpu_session.sql(
                "SELECT NTH_VALUE(score, 2) OVER (PARTITION BY label "
                "ORDER BY score) FROM win_t"
            )

    def test_window_with_group_by_errors(self, tpu_session, scored):
        with pytest.raises(ValueError, match="derived table"):
            tpu_session.sql(
                "SELECT label, ROW_NUMBER() OVER (ORDER BY label) "
                "FROM win_t GROUP BY label"
            )


class TestSubqueries:
    """Derived tables + uncorrelated IN (VERDICT r4 item 3)."""

    @pytest.fixture()
    def views(self, tpu_session):
        tpu_session.createDataFrame(
            [("a.png", "cat", 0.9), ("b.png", "dog", 0.4),
             ("c.png", "cat", 0.7), ("d.png", "owl", 0.5)],
            ["origin", "label", "score"],
        ).createOrReplaceTempView("sq_scored")
        tpu_session.createDataFrame(
            [("cat",), ("dog",)], ["label"]
        ).createOrReplaceTempView("sq_known")

    def test_derived_table(self, tpu_session, views):
        rows = tpu_session.sql(
            "SELECT origin FROM (SELECT origin, score FROM sq_scored "
            "WHERE score > 0.5) t ORDER BY origin"
        ).collect()
        assert [r.origin for r in rows] == ["a.png", "c.png"]

    def test_derived_table_aliased_and_qualified(self, tpu_session, views):
        rows = tpu_session.sql(
            "SELECT t.origin FROM (SELECT * FROM sq_scored) t "
            "WHERE t.label = 'cat' ORDER BY t.origin"
        ).collect()
        assert [r.origin for r in rows] == ["a.png", "c.png"]

    def test_join_against_derived_table(self, tpu_session, views):
        rows = tpu_session.sql(
            "SELECT s.origin, m.cnt FROM sq_scored s JOIN "
            "(SELECT label AS lbl, COUNT(*) AS cnt FROM sq_scored "
            "GROUP BY label) m ON s.label = m.lbl ORDER BY s.origin"
        ).collect()
        assert [(r.origin, r.cnt) for r in rows] == [
            ("a.png", 2), ("b.png", 1), ("c.png", 2), ("d.png", 1),
        ]

    def test_nested_derived_tables(self, tpu_session, views):
        assert tpu_session.sql(
            "SELECT origin FROM (SELECT origin FROM (SELECT * FROM "
            "sq_scored WHERE score > 0.4) a WHERE a.label = 'cat') b"
        ).count() == 2

    def test_in_subquery(self, tpu_session, views):
        rows = tpu_session.sql(
            "SELECT origin FROM sq_scored WHERE label IN "
            "(SELECT label FROM sq_known) ORDER BY origin"
        ).collect()
        assert [r.origin for r in rows] == ["a.png", "b.png", "c.png"]

    def test_not_in_subquery(self, tpu_session, views):
        rows = tpu_session.sql(
            "SELECT origin FROM sq_scored WHERE label NOT IN "
            "(SELECT label FROM sq_known)"
        ).collect()
        assert [r.origin for r in rows] == ["d.png"]

    def test_not_in_subquery_with_null_matches_nothing(
        self, tpu_session, views
    ):
        # the classic SQL trap: NOT IN against a set containing NULL is
        # never TRUE (x != NULL is unknown) — Spark returns zero rows
        tpu_session.createDataFrame(
            [("cat",), (None,)], ["label"]
        ).createOrReplaceTempView("sq_nullset")
        assert tpu_session.sql(
            "SELECT origin FROM sq_scored WHERE label NOT IN "
            "(SELECT label FROM sq_nullset)"
        ).count() == 0

    def test_in_subquery_with_null_keeps_matches(self, tpu_session, views):
        tpu_session.createDataFrame(
            [("cat",), (None,)], ["label"]
        ).createOrReplaceTempView("sq_nullset2")
        rows = tpu_session.sql(
            "SELECT origin FROM sq_scored WHERE label IN "
            "(SELECT label FROM sq_nullset2) ORDER BY origin"
        ).collect()
        assert [r.origin for r in rows] == ["a.png", "c.png"]

    def test_in_subquery_requires_single_column(self, tpu_session, views):
        with pytest.raises(ValueError, match="one column"):
            tpu_session.sql(
                "SELECT origin FROM sq_scored WHERE label IN "
                "(SELECT origin, label FROM sq_scored)"
            )

    def test_temp_subquery_views_are_cleaned_up(self, tpu_session, views):
        before = set(tpu_session.catalog.listTables())
        tpu_session.sql(
            "SELECT * FROM (SELECT * FROM sq_scored) t LIMIT 1"
        ).collect()
        assert set(tpu_session.catalog.listTables()) == before


class TestUnion:
    """UNION [ALL] in the dialect (VERDICT r4 item 6)."""

    @pytest.fixture()
    def views(self, tpu_session):
        tpu_session.createDataFrame(
            [("cat", 1), ("dog", 2)], ["label", "n"]
        ).createOrReplaceTempView("u_a")
        tpu_session.createDataFrame(
            [("cat", 1), ("owl", 3)], ["label", "n"]
        ).createOrReplaceTempView("u_b")

    def test_union_dedupes_union_all_keeps(self, tpu_session, views):
        assert tpu_session.sql(
            "SELECT label, n FROM u_a UNION SELECT label, n FROM u_b"
        ).count() == 3
        assert tpu_session.sql(
            "SELECT label, n FROM u_a UNION ALL SELECT label, n FROM u_b"
        ).count() == 4

    def test_union_positional_names_from_first_branch(
        self, tpu_session, views
    ):
        out = tpu_session.sql(
            "SELECT label AS l, n AS k FROM u_a UNION ALL "
            "SELECT n, label FROM u_b"
        )
        assert out.columns == ["l", "k"]
        assert out.count() == 4

    def test_union_tail_order_and_limit_close_the_union(
        self, tpu_session, views
    ):
        rows = tpu_session.sql(
            "SELECT label FROM u_a UNION ALL SELECT label FROM u_b "
            "ORDER BY label DESC LIMIT 2"
        ).collect()
        assert [r.label for r in rows] == ["owl", "dog"]

    def test_union_count_mismatch_errors(self, tpu_session, views):
        with pytest.raises(ValueError, match="column count"):
            tpu_session.sql(
                "SELECT label, n FROM u_a UNION SELECT label FROM u_b"
            )

    def test_three_way_mixed_union(self, tpu_session, views):
        # left-associative: (a UNION a) has 2 rows, then UNION ALL b
        assert tpu_session.sql(
            "SELECT label FROM u_a UNION SELECT label FROM u_a "
            "UNION ALL SELECT label FROM u_b"
        ).count() == 4

    def test_union_inside_derived_table(self, tpu_session, views):
        rows = tpu_session.sql(
            "SELECT COUNT(*) AS c FROM (SELECT label FROM u_a UNION "
            "SELECT label FROM u_b) t"
        ).collect()
        assert rows[0].c == 3


class TestOrderGroupExpressions:
    """ORDER BY / GROUP BY expressions + qualified names (VERDICT r4
    item 5) — all three probes the verdict verified failing."""

    @pytest.fixture()
    def view(self, tpu_session):
        tpu_session.createDataFrame(
            [("a", 2.6), ("b", 1.2), ("c", 2.4), ("d", 0.6)],
            ["k", "score"],
        ).createOrReplaceTempView("oge_t")

    def test_order_by_qualified(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT k FROM oge_t t ORDER BY t.score"
        ).collect()
        assert [r.k for r in rows] == ["d", "b", "c", "a"]

    def test_order_by_expression(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT k FROM oge_t ORDER BY score + 1 DESC"
        ).collect()
        assert [r.k for r in rows] == ["a", "c", "b", "d"]

    def test_order_by_builtin_call(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT k FROM oge_t ORDER BY ABS(score - 2)"
        ).collect()
        # |score-2|: c=0.4 < a=0.6 < b=0.8 < d=1.4
        assert [r.k for r in rows] == ["c", "a", "b", "d"]

    def test_group_by_cast_expression(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT CAST(score AS int) AS b, COUNT(*) AS c FROM oge_t "
            "GROUP BY CAST(score AS int) ORDER BY b"
        ).collect()
        assert [(r.b, r.c) for r in rows] == [(0, 1), (1, 1), (2, 2)]

    def test_group_by_qualified(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT t.k, COUNT(*) AS c FROM oge_t t GROUP BY t.k "
            "ORDER BY t.k LIMIT 2"
        ).collect()
        assert [(r.k, r.c) for r in rows] == [("a", 1), ("b", 1)]

    def test_agg_order_by_expression_over_outputs(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT k, SUM(score) AS s FROM oge_t GROUP BY k "
            "ORDER BY s * -1"
        ).collect()
        assert [r.k for r in rows] == ["a", "c", "b", "d"]


class TestDialectReviewFixes:
    """Regression tests for the round-5 review findings on the new
    dialect features."""

    @pytest.fixture()
    def dup_view(self, tpu_session):
        tpu_session.createDataFrame(
            [("a", 1), ("a", 1), ("b", 2)], ["k", "n"]
        ).createOrReplaceTempView("dup_t")

    def test_select_distinct_star(self, tpu_session, dup_view):
        assert tpu_session.sql("SELECT DISTINCT * FROM dup_t").count() == 2

    def test_select_distinct_star_with_order(self, tpu_session, dup_view):
        rows = tpu_session.sql(
            "SELECT DISTINCT * FROM dup_t ORDER BY n DESC"
        ).collect()
        assert [(r.k, r.n) for r in rows] == [("b", 2), ("a", 1)]

    def test_unaliased_window_projection(self, tpu_session, dup_view):
        out = tpu_session.sql(
            "SELECT k, ROW_NUMBER() OVER (ORDER BY n) FROM dup_t"
        )
        win_col = [c for c in out.columns if c != "k"][0]
        assert "ROW_NUMBER() OVER" in win_col
        assert sorted(r[win_col] for r in out.collect()) == [1, 2, 3]

    def test_in_subquery_array_values_error_not_flatten(
        self, tpu_session, dup_view
    ):
        # one row holding an array must NOT be unpacked into element
        # membership — it errors (arrays are not comparable to scalars)
        with pytest.raises(ValueError, match="hashable"):
            tpu_session.sql(
                "SELECT k FROM dup_t WHERE n IN "
                "(SELECT COLLECT_LIST(n) FROM dup_t)"
            )

    def test_group_by_expression_case_insensitive_spelling(
        self, tpu_session, dup_view
    ):
        rows = tpu_session.sql(
            "SELECT cast(n AS int) AS b, COUNT(*) AS c FROM dup_t "
            "GROUP BY CAST(n AS int) ORDER BY b"
        ).collect()
        assert [(r.b, r.c) for r in rows] == [(1, 2), (2, 1)]

    def test_multiline_window_projection_alias(self, tpu_session, dup_view):
        # triple-quoted SQL wraps window projections across lines; the
        # alias must still strip (README's own example shape)
        rows = tpu_session.sql(
            """
            SELECT k, rn FROM (
                SELECT k, ROW_NUMBER() OVER
                    (PARTITION BY k ORDER BY n DESC) AS rn
                FROM dup_t
            ) t WHERE t.rn = 1 ORDER BY k
            """
        ).collect()
        assert [(r.k, r.rn) for r in rows] == [("a", 1), ("b", 1)]


class TestAggregateWindows:
    """Aggregate/LAG/LEAD window functions (round-5 extension of the
    ranking windows — the Spark serving-analytics running-total and
    share-of-partition idioms)."""

    @pytest.fixture()
    def view(self, tpu_session):
        tpu_session.createDataFrame(
            [("cat", 1, 0.5), ("cat", 2, 0.3), ("cat", 3, 0.3),
             ("dog", 4, 0.9)],
            ["label", "i", "score"], numPartitions=2,
        ).createOrReplaceTempView("aw_t")

    def test_partition_aggregate_broadcasts(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT i, SUM(score) OVER (PARTITION BY label) AS tot "
            "FROM aw_t"
        ).collect()
        got = {r.i: round(r.tot, 6) for r in rows}
        assert got == {1: 1.1, 2: 1.1, 3: 1.1, 4: 0.9}

    def test_running_aggregate_default_frame(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT i, SUM(score) OVER (PARTITION BY label ORDER BY i) "
            "AS run FROM aw_t"
        ).collect()
        got = {r.i: round(r.run, 6) for r in rows}
        assert got == {1: 0.5, 2: 0.8, 3: 1.1, 4: 0.9}

    def test_running_frame_peers_share(self, tpu_session, view):
        # Spark's default RANGE frame: rows tied on the order key are
        # peers and share the frame end
        rows = tpu_session.sql(
            "SELECT i, COUNT(*) OVER (PARTITION BY label ORDER BY "
            "score) AS c FROM aw_t"
        ).collect()
        got = {r.i: r.c for r in rows}
        assert got == {1: 3, 2: 2, 3: 2, 4: 1}

    def test_count_star_over_empty_spec(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT i, COUNT(*) OVER () AS n FROM aw_t"
        ).collect()
        assert {r.n for r in rows} == {4}

    def test_avg_window_excludes_nulls(self, tpu_session):
        tpu_session.createDataFrame(
            [("a", 2.0), ("a", None), ("a", 4.0)], ["k", "x"]
        ).createOrReplaceTempView("aw_null")
        rows = tpu_session.sql(
            "SELECT AVG(x) OVER (PARTITION BY k) AS m FROM aw_null"
        ).collect()
        assert all(r.m == 3.0 for r in rows)

    def test_lag_lead(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT i, LAG(score) OVER (PARTITION BY label ORDER BY i) "
            "AS prev, LEAD(score, 1, -1.0) OVER (PARTITION BY label "
            "ORDER BY i) AS nxt FROM aw_t"
        ).collect()
        got = {r.i: (r.prev, r.nxt) for r in rows}
        assert got == {
            1: (None, 0.3), 2: (0.5, 0.3), 3: (0.3, -1.0),
            4: (None, -1.0),
        }

    def test_lag_lead_default_type_checked(self, tpu_session, view):
        # a default literal that cannot live in the value column's
        # declared type must be rejected up front, not silently mixed in
        with pytest.raises(ValueError, match="not compatible"):
            tpu_session.sql(
                "SELECT LEAD(score, 1, 'oops') OVER (ORDER BY i) AS nxt "
                "FROM aw_t"
            )
        with pytest.raises(ValueError, match="not compatible"):
            tpu_session.sql(
                "SELECT LAG(i, 1, 2.5) OVER (ORDER BY i) AS p FROM aw_t"
            )
        # int literal into a DOUBLE column widens fine
        rows = tpu_session.sql(
            "SELECT i, LEAD(score, 1, -1) OVER (ORDER BY i) AS nxt "
            "FROM aw_t"
        ).collect()
        assert {r.nxt for r in rows if r.i == 4} == {-1}

    def test_lag_offset_two(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT i, LAG(score, 2) OVER (ORDER BY i) AS p2 FROM aw_t"
        ).collect()
        got = {r.i: r.p2 for r in rows}
        assert got == {1: None, 2: None, 3: 0.5, 4: 0.3}

    def test_share_of_partition_via_derived_table(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT i, score / tot AS share FROM (SELECT i, score, "
            "SUM(score) OVER (PARTITION BY label) AS tot FROM aw_t) d "
            "ORDER BY i"
        ).collect()
        assert [round(r.share, 3) for r in rows] == [
            0.455, 0.273, 0.273, 1.0,
        ]

    def test_rank_still_requires_order(self, tpu_session, view):
        with pytest.raises(ValueError, match="ORDER BY"):
            tpu_session.sql(
                "SELECT ROW_NUMBER() OVER (PARTITION BY label) FROM aw_t"
            )

    def test_window_preserves_partitioning(self, tpu_session, view):
        out = tpu_session.sql(
            "SELECT *, SUM(score) OVER (PARTITION BY label) AS t FROM aw_t"
        )
        assert out.getNumPartitions() == 2

    def test_lag_default_must_be_single_literal(self, tpu_session, view):
        with pytest.raises(ValueError, match="single literal"):
            tpu_session.sql(
                "SELECT LAG(score, 1, 7 + 99) OVER (ORDER BY i) FROM aw_t"
            )

    def test_collect_list_window_schema_is_array(self, tpu_session, view):
        from sparkdl_tpu.sql.types import ArrayType, DoubleType

        out = tpu_session.sql(
            "SELECT i, COLLECT_LIST(score) OVER (PARTITION BY label) "
            "AS xs FROM aw_t"
        )
        assert out.schema["xs"].dataType == ArrayType(DoubleType())


class TestSetOpsAndScalarSubqueries:
    """INTERSECT/EXCEPT [ALL], scalar subqueries, GROUP BY alias
    (round-5 completion of VERDICT r4 missing #3/#4 tails)."""

    @pytest.fixture()
    def views(self, tpu_session):
        tpu_session.createDataFrame(
            [("a", 1), ("a", 1), ("b", 2), ("c", 3)], ["k", "n"]
        ).createOrReplaceTempView("so_x")
        tpu_session.createDataFrame(
            [("a", 1), ("b", 2), ("b", 2), ("d", 4)], ["k", "n"]
        ).createOrReplaceTempView("so_y")
        return tpu_session

    def test_intersect_distinct_and_all(self, views):
        s = views
        assert sorted(r.k for r in s.sql(
            "SELECT k, n FROM so_x INTERSECT SELECT k, n FROM so_y"
        ).collect()) == ["a", "b"]
        # multiset: (a,1) min(2,1)=1, (b,2) min(1,2)=1
        assert sorted(r.k for r in s.sql(
            "SELECT k, n FROM so_x INTERSECT ALL SELECT k, n FROM so_y"
        ).collect()) == ["a", "b"]

    def test_except_distinct_and_all(self, views):
        s = views
        assert [r.k for r in s.sql(
            "SELECT k, n FROM so_x EXCEPT SELECT k, n FROM so_y"
        ).collect()] == ["c"]
        # multiset: (a,1) 2-1=1 survivor, (b,2) 1-2=0, (c,3) 1
        assert sorted(r.k for r in s.sql(
            "SELECT k, n FROM so_x EXCEPT ALL SELECT k, n FROM so_y"
        ).collect()) == ["a", "c"]

    def test_intersect_binds_tighter_than_except(self, tpu_session):
        tpu_session.createDataFrame(
            [("a",), ("b",), ("c",)], ["k"]
        ).createOrReplaceTempView("p_x")
        tpu_session.createDataFrame(
            [("a",), ("b",)], ["k"]
        ).createOrReplaceTempView("p_y")
        tpu_session.createDataFrame(
            [("a",)], ["k"]
        ).createOrReplaceTempView("p_z")
        # x EXCEPT (y INTERSECT z) = {a,b,c} - {a} = {b,c};
        # left-assoc misparse would give (x-y) ∩ z = {c} ∩ {a} = {}
        rows = tpu_session.sql(
            "SELECT k FROM p_x EXCEPT SELECT k FROM p_y "
            "INTERSECT SELECT k FROM p_z"
        ).collect()
        assert sorted(r.k for r in rows) == ["b", "c"]

    def test_setops_with_trailing_order_limit(self, views):
        rows = views.sql(
            "SELECT k, n FROM so_x EXCEPT ALL SELECT k, n FROM so_y "
            "ORDER BY k DESC LIMIT 1"
        ).collect()
        assert [(r.k, r.n) for r in rows] == [("c", 3)]

    def test_dataframe_setop_methods(self, views):
        a, b = views.table("so_x"), views.table("so_y")
        assert sorted(r.k for r in a.subtract(b).collect()) == ["c"]
        assert sorted(r.k for r in a.intersect(b).collect()) == ["a", "b"]
        assert sorted(r.k for r in a.intersectAll(b).collect()) == ["a", "b"]
        assert sorted(r.k for r in a.exceptAll(b).collect()) == ["a", "c"]

    def test_scalar_subquery_in_where(self, views):
        rows = views.sql(
            "SELECT k FROM so_x WHERE n > (SELECT AVG(n) FROM so_x)"
        ).collect()
        assert sorted(r.k for r in rows) == ["b", "c"]

    def test_scalar_subquery_in_projection(self, views):
        # AVG, not MIN: an earlier test registers a scalar UDF named
        # "min" in the shared session (the documented UDF-precedence
        # rule), which would shadow the aggregate here
        rows = views.sql(
            "SELECT k, n - (SELECT AVG(n) FROM so_x) AS d FROM so_x "
            "WHERE k = 'c'"
        ).collect()
        assert [(r.k, r.d) for r in rows] == [("c", 3 - 1.75)]

    def test_scalar_subquery_zero_rows_is_null(self, views):
        rows = views.sql(
            "SELECT k FROM so_x WHERE n = (SELECT n FROM so_y "
            "WHERE k = 'zzz')"
        ).collect()
        assert rows == []  # NULL comparison matches nothing

    def test_scalar_subquery_multirow_errors(self, views):
        with pytest.raises(ValueError, match="[Ss]calar subquery"):
            views.sql(
                "SELECT k FROM so_x WHERE n > (SELECT n FROM so_y)"
            )

    def test_group_by_select_alias(self, views):
        rows = views.sql(
            "SELECT n * 10 AS b, COUNT(*) AS c FROM so_x GROUP BY b "
            "ORDER BY b"
        ).collect()
        assert [(r.b, r.c) for r in rows] == [(10, 2), (20, 1), (30, 1)]

    def test_group_by_alias_of_aggregate_errors(self, views):
        with pytest.raises(ValueError, match="aggregate"):
            views.sql(
                "SELECT COUNT(*) AS c FROM so_x GROUP BY c"
            )

    def test_group_by_real_column_beats_alias(self, views):
        # Spark resolution order: a real column named like an alias
        # wins — so GROUP BY k groups by the string column, and the
        # projection `n AS k` is then not a group key (Spark rejects
        # this query too)
        with pytest.raises(ValueError, match="GROUP BY key"):
            views.sql(
                "SELECT n AS k, COUNT(*) AS c FROM so_x GROUP BY k"
            )


class TestFunctionsSurface:
    """pyspark.sql.functions free-function parity (F.avg/F.desc/F.when/
    F.expr) + the round-5 DataFrame method batch."""

    @pytest.fixture()
    def fdf(self, tpu_session):
        return tpu_session.createDataFrame(
            [("a", 1, 0.5), ("a", 2, 1.5), ("b", 3, 2.5)],
            ["k", "n", "x"], numPartitions=2,
        )

    def test_agg_with_function_columns(self, fdf):
        import sparkdl_tpu.sql.functions as F

        out = fdf.groupBy("k").agg(
            F.avg("x").alias("m"), F.count("*"), F.countDistinct("n")
        )
        assert out.columns == ["k", "m", "count(*)", "count(DISTINCT n)"]
        got = {r.k: (r.m, r["count(*)"]) for r in out.collect()}
        assert got == {"a": (1.0, 2), "b": (2.5, 1)}

    def test_agg_rejects_non_aggregate_column(self, fdf):
        from sparkdl_tpu.sql.functions import col

        with pytest.raises(ValueError, match="not an aggregate"):
            fdf.groupBy("k").agg(col("x"))

    def test_order_by_desc_marker(self, fdf):
        import sparkdl_tpu.sql.functions as F

        assert [r.n for r in fdf.orderBy(F.desc("n")).collect()] == [3, 2, 1]
        assert [
            r.n for r in fdf.orderBy(F.asc("k"), F.desc("x")).collect()
        ] == [2, 1, 3]

    def test_when_otherwise_chain(self, fdf):
        import sparkdl_tpu.sql.functions as F
        from sparkdl_tpu.sql.functions import col

        out = fdf.withColumn(
            "sign",
            F.when(col("x") > 1, "hi").when(col("x") > 0.4, "mid")
            .otherwise("lo"),
        )
        assert [r.sign for r in out.collect()] == ["mid", "hi", "hi"]

    def test_when_guards_division(self, tpu_session):
        import sparkdl_tpu.sql.functions as F
        from sparkdl_tpu.sql.functions import col, lit

        df = tpu_session.createDataFrame([(4.0,), (0.0,)], ["d"])
        out = df.withColumn(
            "q", F.when(col("d") != 0, lit(100.0) / col("d")).otherwise(0.0)
        )
        assert [r.q for r in out.collect()] == [25.0, 0.0]

    def test_otherwise_requires_when(self, fdf):
        from sparkdl_tpu.sql.functions import col

        with pytest.raises(TypeError, match="when"):
            col("x").otherwise(0)

    def test_expr_and_select_expr(self, fdf):
        import sparkdl_tpu.sql.functions as F

        out = fdf.select(F.expr("x * 100").alias("pct"))
        assert [r.pct for r in out.collect()] == [50.0, 150.0, 250.0]
        out2 = fdf.selectExpr("k", "x * 2 AS dbl")
        assert out2.columns == ["k", "dbl"]
        assert [r.dbl for r in out2.collect()] == [1.0, 3.0, 5.0]

    def test_scalar_function_helpers(self, tpu_session):
        import sparkdl_tpu.sql.functions as F

        df = tpu_session.createDataFrame(
            [("Ab", -2, None), (None, 3, "z")], ["s", "i", "t"]
        )
        out = df.select(
            F.upper("s").alias("u"), F.abs("i").alias("a"),
            F.coalesce("s", "t").alias("c"),
            F.concat("s", "t").alias("cat"),
        )
        rows = out.collect()
        assert (rows[0].u, rows[0].a, rows[0].c) == ("AB", 2, "Ab")
        assert (rows[1].u, rows[1].a, rows[1].c) == (None, 3, "z")
        assert rows[0].cat is None  # NULL-propagating concat, as Spark
        out2 = tpu_session.createDataFrame(
            [("hello",)], ["w"]
        ).select(F.substring("w", 2, 3).alias("sub"))
        assert out2.collect()[0].sub == "ell"

    def test_cross_join(self, fdf):
        left = fdf.select("k").withColumnRenamed("k", "k1")
        out = left.crossJoin(fdf.select("n"))
        assert out.count() == 9
        assert out.columns == ["k1", "n"]
        with pytest.raises(ValueError, match="duplicate"):
            fdf.crossJoin(fdf)

    def test_sample(self, fdf):
        assert fdf.sample(1.0).count() == 3
        assert fdf.sample(0.0, 42).count() == 0
        big = fdf.sparkSession.createDataFrame(
            [(i,) for i in range(2000)], ["i"]
        )
        n = big.sample(0.5, seed=7).count()
        assert 850 < n < 1150  # Bernoulli(0.5), ~5 sigma
        m = big.sample(True, 0.5, 7).count()  # Poisson with replacement
        assert 850 < m < 1150

    def test_describe(self, fdf):
        out = fdf.describe("x")
        assert out.columns == ["summary", "x"]
        got = {r.summary: r.x for r in out.collect()}
        assert got["count"] == "3" and got["mean"] == "1.5"
        assert float(got["stddev"]) == pytest.approx(1.0)
        assert got["min"] == "0.5" and got["max"] == "2.5"

    def test_corr_cov_tail_isempty_todf(self, fdf):
        assert fdf.corr("n", "x") == pytest.approx(1.0)
        assert fdf.cov("n", "x") == pytest.approx(1.0)
        assert [r.n for r in fdf.tail(2)] == [2, 3]
        assert not fdf.isEmpty() and fdf.limit(0).isEmpty()
        assert fdf.toDF("a", "b", "c").columns == ["a", "b", "c"]

    def test_with_columns_and_sort_within_partitions(self, fdf):
        from sparkdl_tpu.sql.functions import col

        out = fdf.withColumns(
            {"y": col("x") * 2, "z": col("n") + 1}
        )
        assert out.columns == ["k", "n", "x", "y", "z"]
        import sparkdl_tpu.sql.functions as F

        sp = fdf.sortWithinPartitions(F.desc("n"))
        assert sp.getNumPartitions() == fdf.getNumPartitions()
        # each partition individually descending
        descending = []
        sp.foreachPartition(
            lambda p: descending.append(
                all(a >= b for a, b in zip(p["n"], p["n"][1:]))
            )
        )
        assert all(descending)

    def test_agg_exprs_keyword_back_compat(self, fdf):
        out = fdf.groupBy("k").agg(exprs={"x": "avg"})
        assert {r.k: r["avg(x)"] for r in out.collect()} == {
            "a": 1.0, "b": 2.5,
        }

    def test_zero_arg_scalar_fns_keep_rows(self, fdf):
        import sparkdl_tpu.sql.functions as F

        out = fdf.select(F.concat().alias("c"), F.coalesce().alias("n0"))
        rows = out.collect()
        assert len(rows) == 3
        assert all(r.c == "" and r.n0 is None for r in rows)

    def test_todf_temp_names_cannot_clobber(self, tpu_session):
        df = tpu_session.createDataFrame(
            [(1, 2)], ["b", "__tmp_0"]
        ).toDF("x", "y")
        assert df.columns == ["x", "y"]
        assert df.collect()[0] == Row(x=1, y=2)

    def test_expr_with_alias(self, fdf):
        import sparkdl_tpu.sql.functions as F

        out = fdf.select(F.expr("n AS m"))
        assert out.columns == ["m"]
        assert [r.m for r in out.collect()] == [1, 2, 3]


class TestPivot:
    """GroupedData.pivot (the pyspark wide-reshape idiom)."""

    @pytest.fixture()
    def pdf(self, tpu_session):
        return tpu_session.createDataFrame(
            [("a", "cat", 1.0), ("a", "dog", 2.0), ("b", "cat", 3.0),
             ("a", "cat", 5.0), ("b", None, 9.0)],
            ["k", "animal", "x"], numPartitions=2,
        )

    def test_pivot_single_aggregate(self, pdf):
        out = pdf.groupBy("k").pivot("animal").agg({"x": "sum"})
        # discovered values sorted ascending; NULL pivot groups dropped
        assert out.columns == ["k", "cat", "dog"]
        got = {r.k: (r.cat, r.dog) for r in out.collect()}
        assert got == {"a": (6.0, 2.0), "b": (3.0, None)}

    def test_pivot_explicit_values(self, pdf):
        out = pdf.groupBy("k").pivot("animal", ["cat", "owl"]).agg(
            {"x": "sum"}
        )
        assert out.columns == ["k", "cat", "owl"]
        got = {r.k: (r.cat, r.owl) for r in out.collect()}
        assert got == {"a": (6.0, None), "b": (3.0, None)}

    def test_pivot_multi_aggregate_names(self, pdf):
        import sparkdl_tpu.sql.functions as F

        out = pdf.groupBy("k").pivot("animal").agg(
            F.sum("x").alias("s"), F.count("*").alias("c")
        )
        assert out.columns == ["k", "cat_s", "cat_c", "dog_s", "dog_c"]
        got = {r.k: (r["cat_s"], r["cat_c"]) for r in out.collect()}
        assert got == {"a": (6.0, 2), "b": (3.0, 1)}

    def test_pivot_schema_types(self, pdf):
        from sparkdl_tpu.sql.types import DoubleType, StringType

        out = pdf.groupBy("k").pivot("animal").agg({"x": "sum"})
        assert out.schema["k"].dataType == StringType()
        assert out.schema["cat"].dataType == DoubleType()

    def test_pivot_twice_errors(self, pdf):
        with pytest.raises(ValueError, match="once"):
            pdf.groupBy("k").pivot("animal").pivot("animal")

    def test_pivot_named_helper(self, pdf):
        out = pdf.groupBy("k").pivot("animal").sum("x")
        assert out.columns == ["k", "cat", "dog"]

    def test_pivot_name_collision_raises(self, tpu_session):
        df = tpu_session.createDataFrame(
            [("a", "k", 1.0), ("b", "cat", 2.0)], ["k", "animal", "x"]
        )
        with pytest.raises(ValueError, match="duplicate"):
            df.groupBy("k").pivot("animal").agg({"x": "sum"})
        df2 = tpu_session.createDataFrame(
            [("a", 1, 1.0), ("a", "1", 2.0)], ["k", "v", "x"]
        )
        with pytest.raises(ValueError, match="duplicate"):
            df2.groupBy("k").pivot("v").agg({"x": "sum"})


class TestOrdinalsAndStringBuiltins:
    """ORDER BY / GROUP BY select-list ordinals + the string builtin
    batch (CONCAT/SUBSTRING/TRIM/REPLACE/INSTR/SPLIT)."""

    @pytest.fixture()
    def view(self, tpu_session):
        tpu_session.createDataFrame(
            [("b", 2), ("a", 1), ("c", 3), ("a", 4)], ["k", "n"]
        ).createOrReplaceTempView("ord_t")

    def test_order_by_ordinal(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT k, n FROM ord_t ORDER BY 2 DESC"
        ).collect()
        assert [r.n for r in rows] == [4, 3, 2, 1]

    def test_order_by_ordinal_out_of_range(self, tpu_session, view):
        with pytest.raises(ValueError, match="out of range"):
            tpu_session.sql("SELECT k FROM ord_t ORDER BY 3")

    def test_group_by_ordinal(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT k, COUNT(*) AS c FROM ord_t GROUP BY 1 ORDER BY 1"
        ).collect()
        assert [(r.k, r.c) for r in rows] == [("a", 2), ("b", 1), ("c", 1)]

    def test_group_by_ordinal_of_aggregate_errors(self, tpu_session, view):
        with pytest.raises(ValueError, match="aggregate"):
            tpu_session.sql(
                "SELECT COUNT(*) AS c, k FROM ord_t GROUP BY 1"
            )

    def test_agg_order_by_ordinal_follows_select_order(
        self, tpu_session, view
    ):
        # ordinal 1 is the aggregate (SELECT order), NOT the group key
        rows = tpu_session.sql(
            "SELECT SUM(n) AS sn, k FROM ord_t GROUP BY k ORDER BY 1 DESC"
        ).collect()
        assert [r.k for r in rows] == ["a", "c", "b"]

    def test_union_order_by_ordinal(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT k FROM ord_t UNION SELECT k FROM ord_t "
            "ORDER BY 1 DESC LIMIT 2"
        ).collect()
        assert [r.k for r in rows] == ["c", "b"]

    def test_string_builtins(self, tpu_session):
        tpu_session.createDataFrame(
            [("  hello  ", "path/to/img.png")], ["s", "p"]
        ).createOrReplaceTempView("str_t")
        row = tpu_session.sql(
            "SELECT TRIM(s) AS t, LTRIM(s) AS lt, RTRIM(s) AS rt, "
            "CONCAT(TRIM(s), '!', 42) AS c, SUBSTRING(p, 1, 4) AS sub, "
            "SUBSTR(p, -7) AS tail7, REPLACE(p, '/', ':') AS rp, "
            "INSTR(p, 'img') AS ix, SPLIT(p, '/') AS parts FROM str_t"
        ).collect()[0]
        assert row.t == "hello"
        assert row.lt == "hello  " and row.rt == "  hello"
        assert row.c == "hello!42"
        assert row.sub == "path" and row.tail7 == "img.png"
        assert row.rp == "path:to:img.png"
        assert row.ix == 9
        assert row.parts == ["path", "to", "img.png"]

    def test_string_builtins_null_propagation(self, tpu_session):
        tpu_session.createDataFrame(
            [(None,)], ["s"]
        ).createOrReplaceTempView("str_null")
        row = tpu_session.sql(
            "SELECT CONCAT(s, 'x') AS c, TRIM(s) AS t, "
            "SPLIT(s, ',') AS sp FROM str_null"
        ).collect()[0]
        assert row.c is None and row.t is None and row.sp is None

    def test_substring_negative_start_window(self, tpu_session):
        tpu_session.createDataFrame(
            [("abc",)], ["s"]
        ).createOrReplaceTempView("sub_t")
        row = tpu_session.sql(
            "SELECT SUBSTRING(s, -5, 3) AS a, SUBSTRING(s, -2) AS b, "
            "SUBSTRING(s, -2, 1) AS c FROM sub_t"
        ).collect()[0]
        # Spark: the length window applies before clamping
        assert row.a == "a" and row.b == "bc" and row.c == "b"

    def test_replace_empty_search_is_identity(self, tpu_session):
        tpu_session.createDataFrame(
            [("b",)], ["s"]
        ).createOrReplaceTempView("rep_t")
        row = tpu_session.sql(
            "SELECT REPLACE(s, '', 'x') AS r FROM rep_t"
        ).collect()[0]
        assert row.r == "b"  # Spark: empty search leaves input unchanged

    def test_replace_two_arg_deletes(self, tpu_session):
        tpu_session.createDataFrame(
            [("path/to/img",)], ["p"]
        ).createOrReplaceTempView("rep2_t")
        row = tpu_session.sql(
            "SELECT REPLACE(p, '/') AS r FROM rep2_t"
        ).collect()[0]
        assert row.r == "pathtoimg"

    def test_f_substring_matches_sql_semantics(self, tpu_session):
        import sparkdl_tpu.sql.functions as F

        df = tpu_session.createDataFrame([("abc",)], ["s"])
        out = df.select(
            F.substring("s", -5, 3).alias("a"),
            F.substring("s", 2, 2).alias("b"),
        ).collect()[0]
        assert out.a == "a" and out.b == "bc"


class TestWindowSpecAPI:
    """pyspark Window/over() DataFrame API — the programmatic twin of
    the SQL OVER clause."""

    @pytest.fixture()
    def wdf(self, tpu_session):
        return tpu_session.createDataFrame(
            [("cat", "a", 0.9), ("cat", "b", 0.7), ("dog", "c", 0.6),
             ("dog", "d", 0.95)],
            ["label", "img", "score"], numPartitions=2,
        )

    def test_row_number_over(self, wdf):
        import sparkdl_tpu.sql.functions as F
        from sparkdl_tpu.sql.functions import Window, col

        w = Window.partitionBy("label").orderBy(F.desc("score"))
        r = wdf.withColumn("rn", F.row_number().over(w))
        assert {x.img: x.rn for x in r.collect()} == {
            "a": 1, "b": 2, "c": 2, "d": 1,
        }
        top1 = r.filter(col("rn") == 1)
        assert sorted((x.label, x.img) for x in top1.collect()) == [
            ("cat", "a"), ("dog", "d"),
        ]
        assert r.getNumPartitions() == 2

    def test_mixed_window_select(self, wdf):
        import sparkdl_tpu.sql.functions as F
        from sparkdl_tpu.sql.functions import Window

        w = Window.partitionBy("label").orderBy(F.desc("score"))
        sel = wdf.select(
            "img",
            F.rank().over(w).alias("rk"),
            F.sum("score").over(Window.partitionBy("label")).alias("tot"),
            F.lag("score").over(w).alias("prev"),
            F.lead("score", 1, -1.0).over(w).alias("nxt"),
        )
        got = {x.img: (x.rk, round(x.tot, 2), x.prev, x.nxt)
               for x in sel.collect()}
        assert got == {
            "a": (1, 1.6, None, 0.7), "b": (2, 1.6, 0.9, -1.0),
            "c": (2, 1.55, 0.95, -1.0), "d": (1, 1.55, None, 0.6),
        }

    def test_running_aggregate_over(self, wdf):
        import sparkdl_tpu.sql.functions as F
        from sparkdl_tpu.sql.functions import Window

        w = Window.partitionBy("label").orderBy("score")
        out = wdf.withColumn("run", F.sum("score").over(w))
        got = {x.img: round(x.run, 2) for x in out.collect()}
        assert got == {"a": 1.6, "b": 0.7, "c": 0.6, "d": 1.55}

    def test_errors(self, wdf):
        import sparkdl_tpu.sql.functions as F
        from sparkdl_tpu.sql.functions import Window, col

        with pytest.raises(TypeError, match="WindowSpec"):
            F.row_number().over("nope")
        with pytest.raises(ValueError, match="orderBy"):
            wdf.select(F.row_number().over(Window.partitionBy("label")))
        with pytest.raises(ValueError, match="not a window function"):
            col("score").over(Window.partitionBy("label"))
        with pytest.raises(ValueError, match="over"):
            wdf.select(F.row_number())  # unbound rank fn

    def test_window_replace_existing_column(self, wdf):
        import sparkdl_tpu.sql.functions as F
        from sparkdl_tpu.sql.functions import Window

        w = Window.orderBy("score")
        once = wdf.withColumn("rn", F.row_number().over(w))
        twice = once.withColumn("rn", F.row_number().over(
            Window.orderBy(F.desc("score"))
        ))
        a = {x.img: x.rn for x in once.collect()}
        b = {x.img: x.rn for x in twice.collect()}
        assert a["d"] == 4 and b["d"] == 1  # replaced, not duplicated
        assert twice.columns.count("rn") == 1

    def test_window_replacing_referenced_column(self, wdf):
        import sparkdl_tpu.sql.functions as F
        from sparkdl_tpu.sql.functions import Window

        # replace 'score' with a window computed FROM 'score'
        out = wdf.withColumn(
            "score", F.sum("score").over(Window.partitionBy("label"))
        )
        got = {x.img: round(x.score, 2) for x in out.collect()}
        assert got == {"a": 1.6, "b": 1.6, "c": 1.55, "d": 1.55}
        assert out.columns.count("score") == 1

    def test_shared_spec_single_sort(self, wdf, monkeypatch):
        import sparkdl_tpu.sql.functions as F
        from sparkdl_tpu.sql import dataframe as df_mod
        from sparkdl_tpu.sql.functions import Window

        w = Window.partitionBy("label").orderBy(F.desc("score"))
        sorts = {"n": 0}
        orig = list.sort

        def counting_sort(self, **kw):
            sorts["n"] += 1
            return orig(self, **kw)

        monkeypatch.setattr(
            df_mod.DataFrame, "_window_groups",
            _counting_groups(df_mod.DataFrame._window_groups, sorts),
        )
        out = wdf.select(
            "img",
            F.rank().over(w).alias("rk"),
            F.lag("score").over(w).alias("prev"),
            F.lead("score").over(w).alias("nxt"),
        )
        assert out.count() == 4
        # 3 windows over ONE spec: bucketing+sort computed once, memoized
        assert sorts["n"] == 1


def _counting_groups(orig, counter):
    def wrapped(self, partition_cols, order_cols, ascending,
                extra_cols=()):
        memo = getattr(self, "_win_memo", None)
        key = (tuple(partition_cols), tuple(order_cols), tuple(ascending))
        if memo is None or key not in memo:
            counter["n"] += 1
        return orig(self, partition_cols, order_cols, ascending,
                    extra_cols=extra_cols)
    return wrapped


class TestRankFamilyAndExists:
    """NTILE/PERCENT_RANK/CUME_DIST, FIRST/LAST aggregates, and
    uncorrelated EXISTS."""

    @pytest.fixture()
    def view(self, tpu_session):
        tpu_session.createDataFrame(
            [("a", i, float(i)) for i in range(1, 7)] + [("b", 9, 1.0)],
            ["k", "i", "x"], numPartitions=2,
        ).createOrReplaceTempView("rf_t")

    def test_ntile_percent_rank_cume_dist(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT i, NTILE(3) OVER (PARTITION BY k ORDER BY i) AS b, "
            "PERCENT_RANK() OVER (PARTITION BY k ORDER BY i) AS pr, "
            "CUME_DIST() OVER (PARTITION BY k ORDER BY i) AS cd "
            "FROM rf_t WHERE k = 'a'"
        ).collect()
        assert [r.b for r in rows] == [1, 1, 2, 2, 3, 3]
        assert [round(r.pr, 3) for r in rows] == [
            0.0, 0.2, 0.4, 0.6, 0.8, 1.0,
        ]
        assert [round(r.cd, 3) for r in rows] == [
            round(i / 6, 3) for i in range(1, 7)
        ]

    def test_ntile_uneven_and_single_row(self, tpu_session):
        tpu_session.createDataFrame(
            [(i,) for i in range(1, 6)], ["i"]
        ).createOrReplaceTempView("nt_t")
        rows = tpu_session.sql(
            "SELECT i, NTILE(3) OVER (ORDER BY i) AS b FROM nt_t"
        ).collect()
        # 5 rows into 3 buckets: sizes 2,2,1 (first n%k get one extra)
        assert [r.b for r in rows] == [1, 1, 2, 2, 3]

    def test_cume_dist_with_ties(self, tpu_session):
        tpu_session.createDataFrame(
            [(1,), (2,), (2,), (3,)], ["v"]
        ).createOrReplaceTempView("cd_t")
        rows = tpu_session.sql(
            "SELECT v, CUME_DIST() OVER (ORDER BY v) AS cd FROM cd_t"
        ).collect()
        got = sorted((r.v, round(r.cd, 3)) for r in rows)
        # peers share the INCLUSIVE frame end: both 2s get 3/4
        assert got == [(1, 0.25), (2, 0.75), (2, 0.75), (3, 1.0)]

    def test_first_last_aggregates(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT k, FIRST(x) AS f, LAST(x) AS l FROM rf_t "
            "GROUP BY k ORDER BY k"
        ).collect()
        assert [(r.k, r.f, r.l) for r in rows] == [
            ("a", 1.0, 6.0), ("b", 1.0, 1.0),
        ]

    def test_first_skips_nulls(self, tpu_session):
        tpu_session.createDataFrame(
            [("a", None), ("a", 2.0), ("a", 3.0)], ["k", "x"]
        ).createOrReplaceTempView("fn_t")
        row = tpu_session.sql(
            "SELECT FIRST(x) AS f FROM fn_t GROUP BY k"
        ).collect()[0]
        assert row.f == 2.0  # ignorenulls semantics, documented

    def test_first_last_ignorenulls_argument(self, tpu_session, view):
        # Spark's two-arg spelling: true matches engine semantics and is
        # accepted; false (Spark's default!) cannot be honoured — the
        # engine pre-filters NULLs — so it must fail loudly
        rows = tpu_session.sql(
            "SELECT k, FIRST(x, true) AS f, LAST(x, TRUE) AS l "
            "FROM rf_t GROUP BY k ORDER BY k"
        ).collect()
        assert [(r.k, r.f, r.l) for r in rows] == [
            ("a", 1.0, 6.0), ("b", 1.0, 1.0),
        ]
        with pytest.raises(NotImplementedError, match="ignoreNulls"):
            tpu_session.sql(
                "SELECT FIRST(x, false) AS f FROM rf_t GROUP BY k"
            )
        with pytest.raises(NotImplementedError, match="ignoreNulls"):
            tpu_session.sql(
                "SELECT LAST(x, false) AS f FROM rf_t GROUP BY k"
            )

    def test_first_last_ignorenulls_python_api(self, tpu_session, view):
        import sparkdl_tpu.sql.functions as F

        df = tpu_session.table("rf_t")
        row = (
            df.groupBy("k")
            .agg(F.first("x", ignorenulls=True))
            .orderBy("k")
            .collect()[0]
        )
        assert row["first(x)"] == 1.0
        with pytest.raises(NotImplementedError, match="ignorenulls"):
            F.first("x", ignorenulls=False)
        with pytest.raises(NotImplementedError, match="ignorenulls"):
            F.last("x", ignorenulls=False)

    def test_exists_and_not_exists(self, tpu_session, view):
        assert tpu_session.sql(
            "SELECT k FROM rf_t WHERE EXISTS "
            "(SELECT k FROM rf_t WHERE x > 5)"
        ).count() == 7
        assert tpu_session.sql(
            "SELECT k FROM rf_t WHERE NOT EXISTS "
            "(SELECT k FROM rf_t WHERE x > 99)"
        ).count() == 7
        assert tpu_session.sql(
            "SELECT k FROM rf_t WHERE EXISTS "
            "(SELECT k FROM rf_t WHERE x > 99)"
        ).count() == 0

    def test_window_api_ntile_first(self, tpu_session, view):
        import sparkdl_tpu.sql.functions as F
        from sparkdl_tpu.sql.functions import Window

        df = tpu_session.table("rf_t")
        w = Window.partitionBy("k").orderBy("i")
        out = df.select("i", F.ntile(2).over(w).alias("h"))
        got = [r.h for r in out.collect() if True]
        assert got == [1, 1, 1, 2, 2, 2, 1]
        agg = df.groupBy("k").agg(
            F.first("x").alias("f"), F.last("x").alias("l")
        )
        assert sorted((r.k, r.f, r.l) for r in agg.collect()) == [
            ("a", 1.0, 6.0), ("b", 1.0, 1.0),
        ]

    def test_ntile_requires_positive_literal(self, tpu_session, view):
        import sparkdl_tpu.sql.functions as F

        with pytest.raises(ValueError, match="NTILE"):
            tpu_session.sql(
                "SELECT NTILE(x) OVER (ORDER BY i) FROM rf_t"
            )
        with pytest.raises(ValueError, match="positive"):
            F.ntile(0)

    def test_column_named_exists_still_works(self, tpu_session):
        tpu_session.createDataFrame(
            [(1,), (2,)], ["exists"]
        ).createOrReplaceTempView("ex_t")
        assert tpu_session.sql(
            "SELECT exists FROM ex_t WHERE exists > 1"
        ).count() == 1


class TestRowsFrames:
    """Explicit ROWS BETWEEN frames (moving windows) in SQL and the
    Window spec API."""

    @pytest.fixture()
    def view(self, tpu_session):
        tpu_session.createDataFrame(
            [(i, float(i)) for i in range(1, 7)], ["i", "x"],
            numPartitions=2,
        ).createOrReplaceTempView("fr_t")

    def test_moving_average_sql(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT i, AVG(x) OVER (ORDER BY i ROWS BETWEEN 2 "
            "PRECEDING AND CURRENT ROW) AS ma FROM fr_t"
        ).collect()
        assert [round(r.ma, 3) for r in rows] == [
            1.0, 1.5, 2.0, 3.0, 4.0, 5.0,
        ]

    def test_forward_frame_and_empty_frame_null(self, tpu_session, view):
        rows = tpu_session.sql(
            "SELECT i, SUM(x) OVER (ORDER BY i ROWS BETWEEN 1 "
            "FOLLOWING AND UNBOUNDED FOLLOWING) AS rest FROM fr_t"
        ).collect()
        got = {r.i: r.rest for r in rows}
        assert got[1] == 20.0 and got[5] == 6.0
        assert got[6] is None  # empty frame: SUM of nothing is NULL

    def test_rows_frame_is_row_based_not_peer_shared(self, tpu_session):
        tpu_session.createDataFrame(
            [(1, 1.0), (1, 2.0), (2, 4.0)], ["k", "x"]
        ).createOrReplaceTempView("peer_t")
        rows = tpu_session.sql(
            "SELECT x, COUNT(*) OVER (ORDER BY k ROWS BETWEEN "
            "UNBOUNDED PRECEDING AND CURRENT ROW) AS c FROM peer_t"
        ).collect()
        # ROWS: ties do NOT share (RANGE would give [2, 2, 3])
        assert sorted(r.c for r in rows) == [1, 2, 3]

    def test_window_api_rows_between(self, tpu_session, view):
        import sparkdl_tpu.sql.functions as F
        from sparkdl_tpu.sql.functions import Window

        df = tpu_session.table("fr_t")
        w = Window.orderBy("i").rowsBetween(-2, Window.currentRow)
        out = df.withColumn("ma", F.avg("x").over(w))
        assert [round(r.ma, 3) for r in out.collect()] == [
            1.0, 1.5, 2.0, 3.0, 4.0, 5.0,
        ]
        w2 = Window.orderBy("i").rowsBetween(
            Window.unboundedPreceding, Window.currentRow
        )
        cum = df.withColumn("c", F.count("*").over(w2))
        assert [r.c for r in cum.collect()] == [1, 2, 3, 4, 5, 6]

    def test_frame_validation(self, tpu_session, view):
        import sparkdl_tpu.sql.functions as F
        from sparkdl_tpu.sql.functions import Window

        with pytest.raises(ValueError, match="frame"):
            F.row_number().over(Window.orderBy("i").rowsBetween(-1, 0))
        with pytest.raises(ValueError, match="frame"):
            F.lag("x").over(Window.orderBy("i").rowsBetween(-1, 0))
        with pytest.raises(ValueError, match="after end"):
            Window.orderBy("i").rowsBetween(1, -1)
        with pytest.raises(ValueError, match="ORDER BY"):
            tpu_session.sql(
                "SELECT SUM(x) OVER (ROWS BETWEEN 1 PRECEDING AND "
                "CURRENT ROW) FROM fr_t"
            )

    def test_inverted_sql_frame_errors(self, tpu_session, view):
        with pytest.raises(ValueError, match="after its end"):
            tpu_session.sql(
                "SELECT SUM(x) OVER (ORDER BY i ROWS BETWEEN 2 "
                "FOLLOWING AND 1 PRECEDING) FROM fr_t"
            )

    def test_unbounded_preceding_incremental_matches_naive(
        self, tpu_session, view
    ):
        # (unbounded, -1): the lagged-cumulative shape exercises the
        # empty-frame head AND the incremental accumulator
        rows = tpu_session.sql(
            "SELECT i, SUM(x) OVER (ORDER BY i ROWS BETWEEN UNBOUNDED "
            "PRECEDING AND 1 PRECEDING) AS prior FROM fr_t"
        ).collect()
        got = {r.i: r.prior for r in rows}
        assert got == {1: None, 2: 1.0, 3: 3.0, 4: 6.0, 5: 10.0, 6: 15.0}


class TestUnionByName:
    def test_union_by_name_reorders(self, tpu_session):
        a = tpu_session.createDataFrame([(1, "x")], ["n", "s"])
        b = tpu_session.createDataFrame([("y", 2)], ["s", "n"])
        out = a.unionByName(b)
        assert out.columns == ["n", "s"]
        assert [(r.n, r.s) for r in out.collect()] == [(1, "x"), (2, "y")]

    def test_union_by_name_missing_columns(self, tpu_session):
        a = tpu_session.createDataFrame([(1, "x")], ["n", "s"])
        b = tpu_session.createDataFrame([(2,)], ["n"])
        with pytest.raises(ValueError, match="column sets differ"):
            a.unionByName(b)
        out = a.unionByName(b, allowMissingColumns=True)
        assert out.columns == ["n", "s"]
        rows = out.collect()
        assert rows[1].s is None
        from sparkdl_tpu.sql.types import StringType

        assert out.schema["s"].dataType == StringType()
