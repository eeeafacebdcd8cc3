"""/v1/sql and /v1/logs records, rendered by column (servers/http.py).

The contracts:
  * the document a client parses is the one the per-cell renderer gave:
    `json.loads` of the new bytes equals `json.loads` of the old, values
    AND Python types (a float stays a float, bit for bit), for every
    column type and edge the old renderer could render;
  * a type the Arrow kernels do not cover goes through `_json_value`, a
    column at a time, and is counted apart;
  * a timestamp is rendered from the stored integer: exact, whatever the
    host's time zone;
  * through the real socket, `http.render` is one stage around all of it.
"""

import datetime
import decimal
import json
import time
import urllib.parse
import urllib.request

import numpy as np
import pyarrow as pa
import pytest

from greptimedb_tpu.servers import http
from greptimedb_tpu.utils import metrics, tracing


# ---- the renderer as it was before the columnar one: the oracle -------------

def _old_json_value(v):
    if isinstance(v, datetime.datetime):
        return int(v.timestamp() * 1000)
    if isinstance(v, float) and (np.isnan(v) or np.isinf(v)):
        return None
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    return v


def _old_table_to_greptime_json(table):
    if table is None:
        return {"affectedrows": 0}
    if isinstance(table, int):
        return {"affectedrows": table}
    schema = {
        "column_schemas": [
            {"name": f.name, "data_type": str(f.type)} for f in table.schema
        ]
    }
    rows = []
    cols = [table[c].to_pylist() for c in table.column_names]
    for i in range(table.num_rows):
        rows.append([_old_json_value(col[i]) for col in cols])
    return {"records": {"schema": schema, "rows": rows}}


def _old_document(results) -> bytes:
    return json.dumps({
        "output": [_old_table_to_greptime_json(r) for r in results],
        "execution_time_ms": 0,
    }).encode()


def _parsed(body: bytes) -> str:
    """What a client holds after `json.loads`, as text that tells 100 from
    100.0, -0.0 from 0.0 and one float64 from its neighbour."""
    return repr(json.loads(body))


def _cells_moved():
    before = (metrics.HTTP_RENDER_COLUMNAR_CELLS.total(), metrics.HTTP_RENDER_FALLBACK_CELLS.total())

    def moved():
        return (metrics.HTTP_RENDER_COLUMNAR_CELLS.total() - before[0],
                metrics.HTTP_RENDER_FALLBACK_CELLS.total() - before[1])

    return moved


# ---- the matrix -------------------------------------------------------------

HOUR_MS = 3_600_000
T0_S = 1_451_606_400  # 2016-01-01T00:00:00Z, TSBS's start


def _col(values, type_):
    return pa.table({"c": pa.array(values, type_)})


def _random_doubles():
    bits = np.random.default_rng(28).integers(0, 2**64, 20_000, dtype=np.uint64)
    return pa.table({"c": pa.array(bits.view(np.float64))})  # NaNs and infs among them


def _two_chunks():
    return pa.table({
        "ts": pa.chunked_array(
            [pa.array([T0_S * 1000, None], pa.timestamp("ms")),
             pa.array([T0_S * 1000 + HOUR_MS], pa.timestamp("ms"))]),
        "host": pa.chunked_array([pa.array(["a"]), pa.array(["b", 'c"d'])]),
        "v": pa.chunked_array([pa.array([1.0, 2.5, float("nan")])]),
    })


STRINGS = {
    "plain": ["host_0", "a b", None, "~!#[]{}"],
    "quote": ['say "hi"', "x", None],
    "backslash": ["a\\b", "x", None],
    "newline": ["line1\nline2", "x"],
    "control_char": ["bell\x07", "nul\x00", "tab\t", "esc\x1b", "x"],
    "del_char": ["a\x7fb", "x"],
    "non_ascii": ["héllo", "日本語", "🙂", "x", None],
    "empty": ["", "", None],
}

TABLES = {
    **{f"int{b}": (lambda b=b: _col([-(2 ** (b - 1)), -1, 0, 7, 2 ** (b - 1) - 1, None],
                                    getattr(pa, f"int{b}")()))
       for b in (8, 16, 32, 64)},
    **{f"uint{b}": (lambda b=b: _col([0, 1, 2 ** b - 1, None], getattr(pa, f"uint{b}")()))
       for b in (8, 16, 32)},
    "uint64_above_2p53": lambda: _col([2 ** 53 + 1, 2 ** 64 - 1, 0, None], pa.uint64()),
    "float64_edges": lambda: _col(
        [float("nan"), float("inf"), float("-inf"), -0.0, 0.0, 5e-324, 1e16, 1e22, 1e-5, 1e-7,
         100.0, 1.0, -3.0, 0.1, 1.7976931348623157e308, 2.2250738585072014e-308,
         123456789012345.0, 1234567890123456.0, 2.0 ** 53, 1e15, 1e21, 0.30000000000000004,
         12345678.9, None],
        pa.float64()),
    "float64_whole_only": lambda: _col([100.0, 0.0, -7.0], pa.float64()),
    "float64_no_whole": lambda: _col([0.5, 99.25, None], pa.float64()),
    "float64_random_bits": _random_doubles,
    "float32": lambda: _col(
        [0.1, 1.5, 100.0, float("nan"), float("inf"), float("-inf"), -0.0, 1e-45,
         3.4028235e38, 16777216.0, 50.123456, None],
        pa.float32()),
    "float16": lambda: pa.table({"c": pa.array(
        np.array([0.5, 100.0, np.nan, np.inf, -0.0, 65504.0], np.float16), pa.float16())}),
    "bool": lambda: _col([True, False, None], pa.bool_()),
    **{f"string_{k}": (lambda v=v: _col(v, pa.string())) for k, v in STRINGS.items()},
    "large_string": lambda: _col(["big", 'q"', None, ""], pa.large_string()),
    "dictionary_string": lambda: pa.table(
        {"c": pa.array(["a", "b", "a", None, "b"]).dictionary_encode()}),
    "dictionary_string_escaped": lambda: pa.table(
        {"c": pa.array(['a"', "b\n", 'a"', None]).dictionary_encode()}),
    "timestamp_s": lambda: _col([0, T0_S, T0_S + 3600, -1, None], pa.timestamp("s")),
    "timestamp_ms": lambda: _col(
        [0, T0_S * 1000, T0_S * 1000 + 11 * HOUR_MS, T0_S * 1000 + 500, -1500, None],
        pa.timestamp("ms")),
    "timestamp_us": lambda: _col(
        [0, T0_S * 10**6, T0_S * 10**6 + 250_000, T0_S * 10**6 + 999, -500, None],
        pa.timestamp("us")),
    "timestamp_ns": lambda: _col(
        [0, T0_S * 10**9, T0_S * 10**9 + 250_000_000, T0_S * 10**9 + 400_000, -500_000, None],
        pa.timestamp("ns")),
    "timestamp_ms_utc": lambda: _col([T0_S * 1000, None], pa.timestamp("ms", tz="UTC")),
    "null_type": lambda: _col([None, None], pa.null()),
    "two_chunks": _two_chunks,
    "zero_rows": lambda: pa.table({
        "ts": pa.array([], pa.timestamp("ms")), "host": pa.array([], pa.string()),
        "v": pa.array([], pa.float64())}),
    "zero_columns": lambda: pa.table({}),
    "zero_columns_three_rows": lambda: pa.table({"c": pa.array([1, 2, 3])}).select([]),
    "tsbs_double_groupby": lambda: pa.table({
        "hour": pa.array([T0_S * 1000 + (i // 40) * HOUR_MS for i in range(480)], pa.timestamp("ms")),
        "hostname": pa.array([f"host_{i % 40}" for i in range(480)]),
        "avg": pa.array(np.random.default_rng(5).uniform(0, 100, 480).astype(np.float32).astype(np.float64)),
    }),
    "fallback_list_beside_fast": lambda: pa.table({
        "id": pa.array([1, 2, 3]), "tags": pa.array([[1, 2], [], None], pa.list_(pa.int64())),
        "name": pa.array(["a", None, "c"])}),
    "fallback_struct_beside_fast": lambda: pa.table({
        "v": pa.array([0.5, None]),
        "attrs": pa.array([{"k": "x", "n": 1}, None],
                          pa.struct([("k", pa.string()), ("n", pa.int64())]))}),
    "timestamp_s_year_9999": lambda: _col([253_402_300_799], pa.timestamp("s")),
}

# (columnar columns, fallback columns) of the cases that have a fallback column
FALLBACK_COLUMNS = {
    "fallback_list_beside_fast": (2, 1), "fallback_struct_beside_fast": (1, 1),
}


# the rows are assembled as text in Arrow from `_TEXT_MIN_ROWS` rows on and
# handed to `json` as lists below it: every case goes both ways
ASSEMBLIES = {"as_text": 0, "as_lists": 10**9}


@pytest.fixture(params=sorted(ASSEMBLIES))
def assembly(request, monkeypatch):
    monkeypatch.setattr(http, "_TEXT_MIN_ROWS", ASSEMBLIES[request.param])
    return request.param


@pytest.mark.parametrize("case", sorted(TABLES))
def test_the_parsed_document_is_the_old_renderers(case, assembly):
    table = TABLES[case]()
    moved = _cells_moved()
    new = http._results_json([table])
    assert _parsed(new) == _parsed(_old_document([table]))
    columnar, fallback = FALLBACK_COLUMNS.get(case, (table.num_columns, 0))
    assert moved() == (columnar * table.num_rows, fallback * table.num_rows)


@pytest.mark.parametrize("results", [
    [None], [0], [7], [None, 3, None],
], ids=["none", "zero", "int", "several"])
def test_affected_rows_results_are_untouched(results):
    moved = _cells_moved()
    assert _parsed(http._results_json(results)) == _parsed(_old_document(results))
    assert moved() == (0, 0)


def test_several_results_keep_their_order_in_one_document():
    results = [None, TABLES["tsbs_double_groupby"](), 5, TABLES["zero_rows"](), TABLES["bool"]()]
    assert _parsed(http._results_json(results)) == _parsed(_old_document(results))


@pytest.mark.parametrize("type_, values", [
    (pa.decimal128(10, 2), [decimal.Decimal("1.50"), None]),
    (pa.binary(), [b"\x00\x01", None]),
    (pa.date32(), [datetime.date(2016, 1, 1), None]),
], ids=["decimal", "binary", "date32"])
def test_a_type_json_cannot_hold_is_refused_as_before(type_, values, assembly):
    """No renderer of this server ever gave these a text: `json.dumps`
    raised, the handler answered 500.  They still reach the old per-cell
    path (counted), and it still raises."""
    table = pa.table({"n": pa.array([1, 2]), "x": pa.array(values, type_)})
    with pytest.raises(TypeError, match="not JSON serializable"):
        _old_document([table])
    moved = _cells_moved()
    with pytest.raises(TypeError, match="not JSON serializable"):
        http._results_json([table])
    assert moved() == (2, 2)


def test_the_answer_is_compact_and_float_columns_keep_their_fraction(assembly):
    table = pa.table({"ts": pa.array([T0_S * 1000], pa.timestamp("ms")),
                      "h": pa.array(["host_1"]), "v": pa.array([100.0])})
    assert http._results_json([table]) == (
        b'{"output":[{"records":{"schema":{"column_schemas":['
        b'{"name":"ts","data_type":"timestamp[ms]"},{"name":"h","data_type":"string"},'
        b'{"name":"v","data_type":"double"}]},"rows":[[1451606400000,"host_1",100.0]]}}],'
        b'"execution_time_ms":0}'
    )


def test_the_number_of_rows_alone_picks_the_assembly(monkeypatch):
    texts = []
    plain = http._json_text
    monkeypatch.setattr(http, "_json_text", lambda ready: texts.append(len(ready)) or plain(ready))
    for rows in (1, http._TEXT_MIN_ROWS - 1, http._TEXT_MIN_ROWS, 4 * http._TEXT_MIN_ROWS):
        table = pa.table({"n": pa.array(range(rows)), "v": pa.array(np.arange(rows) / 4)})
        moved = _cells_moved()
        assert _parsed(http._results_json([table])) == _parsed(_old_document([table]))
        assert moved() == (2 * rows, 0)  # by column either way
    assert texts == [http._TEXT_MIN_ROWS] * 2 + [4 * http._TEXT_MIN_ROWS] * 2


# ---- the one repair: timestamps from the stored integer ----------------------

def _first_column(body: bytes) -> list:
    return [row[0] for row in json.loads(body)["output"][0]["records"]["rows"]]


def test_a_timestamp_is_the_stored_integer_where_the_float_path_was_a_millisecond_off(assembly):
    # `datetime.timestamp() * 1000` falls a hair under these whole
    # milliseconds (about six in a thousand do), and `int()` cut it
    stored = [1_079_337_347_472, 541_169_507_341, 2_165_497_250_430]
    table = _col(stored, pa.timestamp("ms"))
    assert _first_column(_old_document([table])) == [ms - 1 for ms in stored]
    assert _first_column(http._results_json([table])) == stored
    # and a nanosecond count just under a millisecond was rounded UP into it
    table = _col([T0_S * 10**9 + 999_999], pa.timestamp("ns"))
    assert _first_column(_old_document([table])) == [T0_S * 1000 + 1]
    assert _first_column(http._results_json([table])) == [T0_S * 1000]


def test_a_zoneless_timestamp_does_not_move_with_the_hosts_time_zone(assembly, monkeypatch):
    table = _col([T0_S * 1000, T0_S * 1000 + HOUR_MS], pa.timestamp("ms"))
    monkeypatch.setenv("TZ", "Asia/Tokyo")
    time.tzset()
    try:
        old = _first_column(_old_document([table]))
        new = _first_column(http._results_json([table]))
    finally:
        monkeypatch.delenv("TZ")
        time.tzset()
    assert new == [T0_S * 1000, T0_S * 1000 + HOUR_MS]
    assert old == [T0_S * 1000 - 9 * HOUR_MS, T0_S * 1000 - 8 * HOUR_MS]


# ---- through the real socket -------------------------------------------------

ROWS = 50_000


@pytest.fixture()
def server(tmp_path):
    from greptimedb_tpu.database import Database
    from greptimedb_tpu.servers.http import HttpServer

    db = Database(data_home=str(tmp_path / "db"))
    db.sql("CREATE TABLE big (host STRING, ts TIMESTAMP TIME INDEX, v DOUBLE, PRIMARY KEY (host))")
    rng = np.random.default_rng(3)
    db.insert_rows("big", pa.table({
        "host": pa.array([f"host_{i % 100}" for i in range(ROWS)]),
        "ts": pa.array(T0_S * 1000 + np.arange(ROWS, dtype=np.int64) // 100 * 10_000,
                       pa.timestamp("ms")),
        "v": pa.array(rng.uniform(0, 100, ROWS)),
    }))
    srv = HttpServer(db, "127.0.0.1:0").start()
    yield db, srv
    srv.stop()
    db.close()


def test_a_50000_row_answer_through_the_socket_is_one_columnar_render(server, monkeypatch):
    db, srv = server
    sql = "SELECT ts, host, v FROM big ORDER BY host, ts"
    expected = db.sql_one(sql)
    assert expected.num_rows == ROWS

    seen = []  # (event, columnar cells so far) at the edges of every http.render
    plain_enter, plain_exit = tracing.stage.__enter__, tracing.stage.__exit__

    def enter_and_note(self):
        if self.name == "http.render":
            seen.append(("enter", metrics.HTTP_RENDER_COLUMNAR_CELLS.total()))
        return plain_enter(self)

    def exit_and_note(self, *exc):
        out = plain_exit(self, *exc)
        if self.name == "http.render":
            seen.append(("exit", metrics.HTTP_RENDER_COLUMNAR_CELLS.total()))
        return out

    monkeypatch.setattr(tracing.stage, "__enter__", enter_and_note)
    monkeypatch.setattr(tracing.stage, "__exit__", exit_and_note)
    moved = _cells_moved()
    render_before = metrics.STAGE_SELF_S_HTTP_RENDER.total()
    body = urllib.parse.urlencode({"sql": sql}).encode()
    with urllib.request.urlopen(f"http://{srv.address}/v1/sql", data=body, timeout=120) as r:
        assert r.status == 200
        raw = r.read()
        assert int(r.headers["Content-Length"]) == len(raw)
    deadline = time.monotonic() + 10.0
    while len(seen) < 2 and time.monotonic() < deadline:
        time.sleep(0.001)

    assert _parsed(raw) == _parsed(_old_document([expected]))
    rows = json.loads(raw)["output"][0]["records"]["rows"]
    assert len(rows) == ROWS and rows[0][1] == "host_0" and isinstance(rows[0][0], int)
    assert rows[0][0] == T0_S * 1000 and isinstance(rows[0][2], float)
    assert moved() == (ROWS * 3, 0)
    # one http.render, and every cell was rendered inside it
    assert [event for event, _cells in seen] == ["enter", "exit"]
    assert seen[1][1] - seen[0][1] == ROWS * 3
    assert metrics.STAGE_SELF_S_HTTP_RENDER.total() > render_before
