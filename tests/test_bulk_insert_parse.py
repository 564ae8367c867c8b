"""A bulk INSERT's literals take a short way through the lexer and the
parser (sql/lexer.py `_BULK`, sql/parser.py `_values_row`): both must make
exactly what the general way makes."""

import random

import pytest

from matrixone_tpu.sql import ast, lexer
from matrixone_tpu.sql.parser import Parser, parse_one

TRICKY = [
    "insert into t values (1, 'a', 2.50), (3,'b' ,4.0 )",
    "insert into t values (-1, +2, - 3.5, 1e5, 1.5e-3, .5, 1., 1.2.3)",
    "insert into t values ('it''s', 'a\\'b', 'tab\\t', '', ' ', 'x' 'y')",
    "insert into t values (\"dq\", `id`, 'a,b', 'a)b', '(', ')', ',')",
    "insert into t values (1 + 2, (3), ((4)), 5 * (6 - 7), 'a' like 'b', 'c' || 'd')",
    "insert into t values (null, true, false, date '2020-01-02', ?, ?)",
    "insert into t values (1) -- tail, 'x'\n, (2) /* (3), */ , (4)",
    "insert into t values (12abc, 1_000, 7 e, 8e, 9e+, 0x1F)",
    "select a, (b), 'c', 1, 2.5 from t where x in (1, 2, 'three') and y = (4)",
    "select f(1, 'a', (2.5)), g() from t limit 10, 20",
    "insert into t values (١, 'é', 1 , 2)",
    "insert into t(a, b) values\n(1,\n'a'\n)\n,\n(2\t,\t'b'\t)",
    "(((,,,)))",
    "1,2,3",
    "'unterminated, 1",
]


def _general_tokens(sql, monkeypatch):
    with monkeypatch.context() as m:
        m.setattr(lexer, "_BULK", lambda sql, i: None)
        return _tokens(sql)


def _tokens(sql):
    try:
        return [(t.kind, t.value, t.pos) for t in lexer.tokenize(sql)]
    except lexer.LexError as e:
        return ("LexError", str(e))


@pytest.mark.parametrize("sql", TRICKY)
def test_bulk_tokens_equal_the_general_lexers(sql, monkeypatch):
    assert _tokens(sql) == _general_tokens(sql, monkeypatch)


@pytest.mark.parametrize("seed", range(8))
def test_bulk_tokens_equal_the_general_lexers_on_random_text(seed,
                                                             monkeypatch):
    rng = random.Random(seed)
    pieces = ["1", "23", "4.5", "0.06", "'a'", "'b c'", "''", "'d''e'",
              "'f\\'g'", ",", ", ", "(", ")", " ", "\n", "-", "+", "1e3",
              ".5", "7.", "x", "null", "--c\n", "/*c*/", "\"q\"", "`i`",
              "<=", "||", "?", "8.9.1", "'h,i'", "'j)k'"]
    for _ in range(200):
        sql = "".join(rng.choice(pieces) for _ in range(rng.randint(1, 30)))
        assert _tokens(sql) == _general_tokens(sql, monkeypatch), sql


def _ladder_rows(sql):
    """The rows as the precedence ladder alone parses them."""
    p = Parser(lexer.tokenize(sql), src=sql)
    for kw in ("insert", "into"):
        p.expect_kw(kw)
    p.ident()
    p.expect_kw("values")
    rows = []
    while True:
        p.expect_op("(")
        row = [p.expr()]
        while p.accept_op(","):
            row.append(p.expr())
        p.expect_op(")")
        rows.append(row)
        if not p.accept_op(","):
            return rows


@pytest.mark.parametrize("sql", [
    TRICKY[0],
    "insert into t values (-1, +2, - 3.5, 1e5, 1.5e-3, .5, 1.)",
    "insert into t values ('it''s', 'a\\'b', 'tab\\t', '', ' ')",
    "insert into t values (\"dq\", 'a,b', 'a)b', '(', ')', ',')",
    "insert into t values (1 + 2, (3), ((4)), 5 * (6 - 7), 'a' like 'b')",
    TRICKY[5],
    "insert into t values\n(1,\n'a'\n)\n,\n(2\t,\t'b'\t)"])
def test_values_rows_equal_the_ladders(sql):
    assert parse_one(sql).rows == _ladder_rows(sql)


def test_values_row_literals_keep_kind_and_text():
    stmt = parse_one("insert into t values (7, 2.50, 'x', -3, 1 + 1), (8)")
    first = stmt.rows[0]
    assert [(type(e), getattr(e, "kind", None), getattr(e, "value", None))
            for e in first[:4]] == [
        (ast.Literal, "int", 7), (ast.Literal, "float", "2.50"),
        (ast.Literal, "str", "x"), (ast.Literal, "int", -3)]
    assert isinstance(first[4], ast.BinaryOp)
    assert stmt.rows[1] == [ast.Literal(8, "int")]


@pytest.mark.parametrize("sql", ["insert into t values (1, 2",
                                 "insert into t values (1 2)",
                                 "insert into t values (1,)",
                                 "insert into t values ()"])
def test_a_broken_values_row_is_still_refused(sql):
    with pytest.raises(Exception) as e:
        parse_one(sql)
    assert type(e.value).__name__ in ("ParseError", "LexError")
