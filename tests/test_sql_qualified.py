"""Qualified column references: ``t.col``, relation aliases, dotted ON.

Resolution is scope-based: each FROM/JOIN relation contributes an alias
(explicit ``[AS] alias`` or its view name) mapping source columns to the
flat join-output columns — USING keys keep their name, a non-key column
present on both sides resolves the right relation's ref to Spark's
``<name>_right`` rename. A literal dotted column name on the frame wins
over qualified interpretation (CSV headers may contain dots).
"""

import pytest

from sparkdq4ml_tpu import Frame


@pytest.fixture
def views(session):
    t = Frame({"guest": [2.0, 10.0, 14.0], "price": [30.0, 95.0, 120.0]})
    t.create_or_replace_temp_view("t")
    g = Frame({"guest": [10.0, 14.0], "price": [1.0, 2.0],
               "tag": [7.0, 8.0]})
    g.create_or_replace_temp_view("g")
    return t, g


class TestQualifiedRefs:
    def test_view_name_qualifier(self, session, views):
        out = session.sql("SELECT t.price FROM t WHERE t.guest > 5")
        assert out.to_pydict()["price"].tolist() == [95.0, 120.0]
        assert out.columns == ["price"]        # output name is flat

    def test_as_alias_and_bare_alias(self, session, views):
        for sql in ("SELECT x.price FROM t AS x WHERE x.guest > 5",
                    "SELECT x.price FROM t x WHERE x.guest > 5"):
            assert session.sql(sql).to_pydict()["price"].tolist() == \
                [95.0, 120.0]

    def test_alias_replaces_view_name(self, session, views):
        with pytest.raises(ValueError, match="unknown relation alias"):
            session.sql("SELECT t.price FROM t AS x")

    def test_join_disambiguation(self, session, views):
        out = session.sql(
            "SELECT t.price, g.price, g.tag FROM t JOIN g USING (guest)")
        d = out.to_pydict()
        assert d["price"].tolist() == [95.0, 120.0]       # left side
        assert d["price_right"].tolist() == [1.0, 2.0]    # right side
        assert d["tag"].tolist() == [7.0, 8.0]

    def test_qualified_on_clause(self, session, views):
        out = session.sql("SELECT t.price FROM t JOIN g "
                          "ON t.guest = g.guest")
        assert out.to_pydict()["price"].tolist() == [95.0, 120.0]

    def test_qualified_on_different_columns_rejected(self, session, views):
        # g carries a ``guest`` of its own, which the join on t.guest =
        # g.tag would lose: refused (two different names join where the
        # right side has no column of the left key's name)
        with pytest.raises(ValueError, match="shared column name"):
            session.sql("SELECT t.price FROM t JOIN g ON t.guest = g.tag")
        # both columns of one side: never an equi-join
        with pytest.raises(ValueError, match="shared column name"):
            session.sql("SELECT t.price FROM t JOIN g ON t.guest = t.price")

    def test_aggregates_and_post_agg(self, session, views):
        assert session.sql("SELECT max(t.price) AS mp FROM t") \
            .to_pydict()["mp"].tolist() == [120.0]
        assert session.sql(
            "SELECT max(t.price) - min(t.price) AS sp FROM t") \
            .to_pydict()["sp"].tolist() == [90.0]

    def test_group_and_order_qualified(self, session, views):
        out = session.sql("SELECT t.guest, count(*) AS n FROM t "
                          "GROUP BY t.guest ORDER BY t.guest DESC")
        assert out.to_pydict()["guest"].tolist() == [14.0, 10.0, 2.0]

    def test_unknown_alias_and_column_errors(self, session, views):
        with pytest.raises(ValueError, match="unknown relation alias"):
            session.sql("SELECT z.price FROM t")
        with pytest.raises(ValueError, match="not found in relation"):
            session.sql("SELECT t.nope FROM t")

    def test_semi_join_right_limited_to_keys(self, session, views):
        out = session.sql("SELECT t.price FROM t LEFT SEMI JOIN g "
                          "USING (guest)")
        assert out.to_pydict()["price"].tolist() == [95.0, 120.0]
        with pytest.raises(ValueError, match="not found in relation"):
            session.sql("SELECT g.tag FROM t LEFT SEMI JOIN g USING (guest)")

    def test_literal_dotted_column_wins(self, session):
        f = Frame({"a.b": [1.0, 2.0], "c": [3.0, 4.0]})
        f.create_or_replace_temp_view("dotted")
        out = session.sql("SELECT a.b FROM dotted WHERE a.b > 1")
        assert out.to_pydict()["a.b"].tolist() == [2.0]

    def test_qualified_inside_in_subquery(self, session, views):
        out = session.sql("SELECT t.price FROM t WHERE t.guest IN "
                          "(SELECT guest FROM g)")
        assert out.to_pydict()["price"].tolist() == [95.0, 120.0]

    def test_unaliased_derived_before_setop_and_offset(self, session, views):
        # INTERSECT/OFFSET after an unaliased derived table must start
        # the clause, not become the table's alias.
        assert session.sql("SELECT price FROM (SELECT price FROM t) "
                           "INTERSECT SELECT price FROM t").count() == 3
        assert session.sql("SELECT price FROM (SELECT price FROM t) "
                           "OFFSET 2").count() == 1

    def test_derived_table_alias(self, session, views):
        out = session.sql("SELECT s.price FROM "
                          "(SELECT guest, price FROM t) s "
                          "WHERE s.guest > 5")
        assert out.to_pydict()["price"].tolist() == [95.0, 120.0]

    def test_join_derived_alias(self, session, views):
        out = session.sql(
            "SELECT t.price, x.tag FROM t JOIN "
            "(SELECT guest, tag FROM g) x USING (guest)")
        assert out.to_pydict()["tag"].tolist() == [7.0, 8.0]
