"""Metric engine: logical tables multiplexed onto one physical region.

Mirrors the reference's metric-engine tests (reference
src/metric-engine/src/engine.rs tests + sqlness cases under
tests/cases/standalone/common/create/create_metric_table.sql).
"""

import pyarrow as pa
import pytest

from greptimedb_tpu.database import Database
from greptimedb_tpu.metric.engine import TABLE_ID_COL, TSID_COL, tsid_hash


@pytest.fixture()
def db(tmp_path):
    d = Database(data_home=str(tmp_path / "data"))
    yield d
    d.close()


def _create_phy(db):
    db.sql("CREATE TABLE phy (ts TIMESTAMP TIME INDEX, val DOUBLE) "
           "WITH ('physical_metric_table' = '')")


def test_create_physical_and_logical(db):
    _create_phy(db)
    db.sql(
        "CREATE TABLE t1 (ts TIMESTAMP TIME INDEX, val DOUBLE, "
        "host STRING PRIMARY KEY) WITH ('on_physical_table' = 'phy')"
    )
    phys = db.catalog.table("phy")
    assert phys.schema.has_column(TABLE_ID_COL)
    assert phys.schema.has_column(TSID_COL)
    assert phys.schema.has_column("host")  # label propagated to physical
    logical = db.catalog.table("t1")
    assert logical.schema.column_names() == ["ts", "val", "host"]


def test_write_and_read_logical(db):
    _create_phy(db)
    db.sql(
        "CREATE TABLE t1 (ts TIMESTAMP TIME INDEX, val DOUBLE, "
        "host STRING PRIMARY KEY) WITH ('on_physical_table' = 'phy')"
    )
    db.sql("INSERT INTO t1 (ts, val, host) VALUES (1000, 1.5, 'a'), (2000, 2.5, 'b')")
    out = db.sql_one("SELECT ts, val, host FROM t1 ORDER BY ts")
    assert out["val"].to_pylist() == [1.5, 2.5]
    assert out["host"].to_pylist() == ["a", "b"]
    # Physical table carries the synthetic columns.
    phys = db.sql_one("SELECT ts, __table_id, __tsid, host FROM phy ORDER BY ts")
    tid = db.catalog.table("t1").table_id
    assert phys["__table_id"].to_pylist() == [tid, tid]
    assert phys["host"].to_pylist() == ["a", "b"]
    assert len(set(phys["__tsid"].to_pylist())) == 2  # distinct series


def test_two_logical_tables_isolated(db):
    _create_phy(db)
    for t in ("m1", "m2"):
        db.sql(
            f"CREATE TABLE {t} (ts TIMESTAMP TIME INDEX, val DOUBLE, "
            f"host STRING PRIMARY KEY) WITH ('on_physical_table' = 'phy')"
        )
    db.sql("INSERT INTO m1 (ts, val, host) VALUES (1000, 1.0, 'x')")
    db.sql("INSERT INTO m2 (ts, val, host) VALUES (1000, 9.0, 'x'), (2000, 8.0, 'y')")
    assert db.sql_one("SELECT count(*) FROM m1").column(0).to_pylist() == [1]
    assert db.sql_one("SELECT count(*) FROM m2").column(0).to_pylist() == [2]
    # Filters on labels work per logical table.
    out = db.sql_one("SELECT val FROM m2 WHERE host = 'y'")
    assert out["val"].to_pylist() == [8.0]


def test_label_widening_on_demand(db):
    _create_phy(db)
    meta = db.metric.ensure_logical_table("m", ["host"], "phy")
    db.insert_rows(
        "m",
        pa.table({"ts": pa.array([1000], pa.timestamp("ms")),
                  "val": [1.0], "host": ["a"]}),
    )
    # New label appears → logical + physical schemas widen in place.
    meta = db.metric.ensure_logical_table("m", ["host", "dc"], "phy")
    assert meta.schema.has_column("dc")
    assert db.catalog.table("phy").schema.has_column("dc")
    db.insert_rows(
        "m",
        pa.table({"ts": pa.array([2000], pa.timestamp("ms")),
                  "val": [2.0], "host": ["a"], "dc": ["eu"]}),
    )
    out = db.sql_one("SELECT ts, val, dc FROM m ORDER BY ts")
    assert out["dc"].to_pylist() == [None, "eu"]
    # Old rows (pre-widening) must NOT match a dc filter.
    out = db.sql_one("SELECT val FROM m WHERE dc = 'eu'")
    assert out["val"].to_pylist() == [2.0]


def test_widening_survives_flush(db):
    _create_phy(db)
    db.metric.ensure_logical_table("m", ["host"], "phy")
    db.insert_rows(
        "m", pa.table({"ts": pa.array([1000], pa.timestamp("ms")), "val": [1.0],
                       "host": ["a"]}),
    )
    db.sql("ADMIN flush_table('m')")  # old rows now in an SST without `dc`
    db.metric.ensure_logical_table("m", ["host", "dc"], "phy")
    db.insert_rows(
        "m", pa.table({"ts": pa.array([2000], pa.timestamp("ms")), "val": [2.0],
                       "host": ["a"], "dc": ["eu"]}),
    )
    out = db.sql_one("SELECT val FROM m WHERE dc = 'eu'")
    assert out["val"].to_pylist() == [2.0]
    out = db.sql_one("SELECT count(*) FROM m")
    assert out.column(0).to_pylist() == [2]


def test_drop_rules(db):
    _create_phy(db)
    db.sql("CREATE TABLE t1 (ts TIMESTAMP TIME INDEX, val DOUBLE, "
           "host STRING PRIMARY KEY) WITH ('on_physical_table' = 'phy')")
    with pytest.raises(Exception):
        db.sql("DROP TABLE phy")  # still hosts t1
    db.sql("DROP TABLE t1")
    db.sql("DROP TABLE phy")
    assert not db.catalog.has_table("phy")


def test_reopen_after_restart(tmp_path):
    home = str(tmp_path / "data")
    db = Database(data_home=home)
    _create_phy(db)
    db.sql("CREATE TABLE t1 (ts TIMESTAMP TIME INDEX, val DOUBLE, "
           "host STRING PRIMARY KEY) WITH ('on_physical_table' = 'phy')")
    db.sql("INSERT INTO t1 (ts, val, host) VALUES (1000, 1.5, 'a')")
    db.close()
    db2 = Database(data_home=home)
    out = db2.sql_one("SELECT val, host FROM t1")
    assert out["val"].to_pylist() == [1.5]
    assert db2.metric.logical_tables("phy") == ["t1"]
    db2.close()


def test_mismatched_ts_val_names_remap(db):
    db.sql("CREATE TABLE phy2 (ts TIMESTAMP TIME INDEX, v DOUBLE) "
           "WITH ('physical_metric_table' = '')")
    db.sql("CREATE TABLE m (t TIMESTAMP TIME INDEX, value DOUBLE, "
           "host STRING PRIMARY KEY) WITH ('on_physical_table' = 'phy2')")
    db.sql("INSERT INTO m (t, value, host) VALUES (1000, 7.5, 'a')")
    out = db.sql_one("SELECT t, value, host FROM m")
    assert out["value"].to_pylist() == [7.5]
    phys = db.sql_one("SELECT v FROM phy2")
    assert phys["v"].to_pylist() == [7.5]  # remapped into the physical value column


def test_admin_on_logical_redirects(db):
    _create_phy(db)
    db.sql("CREATE TABLE m (ts TIMESTAMP TIME INDEX, val DOUBLE, "
           "host STRING PRIMARY KEY) WITH ('on_physical_table' = 'phy')")
    db.sql("INSERT INTO m (ts, val, host) VALUES (1000, 1.0, 'a')")
    db.sql("ADMIN flush_table('m')")
    db.sql("ADMIN compact_table('m')")  # must redirect, not touch ghost regions


def test_drop_and_recreate_physical_starts_clean(db):
    _create_phy(db)
    db.sql("CREATE TABLE m (ts TIMESTAMP TIME INDEX, val DOUBLE, "
           "host STRING PRIMARY KEY) WITH ('on_physical_table' = 'phy')")
    db.sql("DROP TABLE m")
    db.sql("DROP TABLE phy")
    _create_phy(db)
    assert db.metric.logical_tables("phy") == []


def test_tsid_stability():
    a = tsid_hash([("host", "a"), ("dc", "eu")])
    b = tsid_hash([("dc", "eu"), ("host", "a")])
    assert a == b  # order-insensitive
    assert a != tsid_hash([("host", "b"), ("dc", "eu")])


def test_a_label_named_like_the_metric_name_is_refused(db):
    """`__name__` is the metric itself, the logical table's name, and a
    series id already hashes it as such: no label column may take it."""
    from greptimedb_tpu.utils.errors import InvalidArgumentsError

    _create_phy(db)
    with pytest.raises(InvalidArgumentsError, match="__name__"):
        db.sql(
            "CREATE TABLE t1 (ts TIMESTAMP TIME INDEX, val DOUBLE, "
            "__name__ STRING PRIMARY KEY) WITH ('on_physical_table' = 'phy')"
        )
    assert not db.catalog.has_table("t1")
