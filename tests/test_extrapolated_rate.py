"""`rate` / `increase` against Prometheus' `extrapolatedRate`, as the
benchmark's plain reference (`benchmark/promql_ref.py`) computes it: the
tile path and the legacy scan, over counters that restart.

The zero-point clamp reads the RAW first sample of a window.  A counter that
restarted inside the fetched range but before a window is where the
reset-adjusted first sample (raw + every earlier drop) and the raw one part:
clamped with the adjusted one, as before PR 29, the `before` and `zero`
series below come out wrong by 3 %.
"""

import os
import sys
import tempfile

import numpy as np
import pyarrow as pa
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

from benchmark import program, promql_ref  # noqa: E402
from greptimedb_tpu.utils import metrics as m  # noqa: E402

T0, SCRAPE_S, TICKS = 1767225600000, 10, 120
START_S, END_S, STEP_S, RANGE_S = 600, 1140, 60, 300
# series -> (restart tick, the sample of that scrape); the counter then grows
# by 1.0 to the next scrape, so that the zero point lies close.  The fetched
# range is (300 s, 1140 s], the windows are (t - 300 s, t] for t = 600 s ... 1140 s
SERIES = {
    "steady": None,  # no restart
    "before": (42, 3.5),  # at 420 s: just before the window of t = 720 s, which starts with 4.5
    "inside": (100, 7.25),  # at 1000 s: inside the last three windows, before none
    "zero": (61, 0.0),  # at 610 s: the first sample of the window of t = 900 s, and 0
}


class Fleet:
    """What the reference reads of a dataset."""

    t0, scrape_s, ticks = T0, SCRAPE_S, TICKS

    @staticmethod
    def tick_ts():
        return T0 + np.arange(TICKS, dtype=np.int64) * (SCRAPE_S * 1000)


def _samples() -> dict:
    rng = np.random.default_rng(29)
    out = {}
    for name, restart in SERIES.items():
        grows = rng.uniform(0.0, 100.0, TICKS)
        if restart is not None:
            tick, level = restart
            grows[tick + 1] = 1.0
        v = 1.3e6 + np.cumsum(grows)
        if restart is not None:
            v[tick:] += level - v[tick]
        out[name] = v
    return out


def _query(func: str, series: str) -> str:
    return (
        f"TQL EVAL ({T0 // 1000 + START_S}, {T0 // 1000 + END_S}, '{STEP_S}s') "
        f"{func}(ctr{{host=\"{series}\"}}[{RANGE_S // 60}m])"
    )


@pytest.fixture(scope="module")
def loaded():
    from greptimedb_tpu.database import Database
    from greptimedb_tpu.utils.config import Config

    cfg = Config()
    cfg.storage.data_home = tempfile.mkdtemp()
    db = Database(config=cfg)
    samples = _samples()
    db.sql(
        "CREATE TABLE ctr (host STRING, greptime_value DOUBLE, "
        "ts TIMESTAMP(3) TIME INDEX, PRIMARY KEY (host))"
    )
    names = sorted(samples)
    db.insert_rows("ctr", pa.table({
        "host": pa.array(np.repeat(names, TICKS)),
        "greptime_value": pa.array(np.concatenate([samples[n] for n in names])),
        "ts": pa.array(np.tile(Fleet.tick_ts(), len(names)), pa.timestamp("ms")),
    }))
    db.sql("ADMIN flush_table('ctr')")
    db.sql_one(_query("rate", "steady"))  # cold: the legacy scan answers, the planes build
    program.wait_builds(db)
    try:
        yield db, samples
    finally:
        db.close()


@pytest.mark.parametrize("series", list(SERIES))
@pytest.mark.parametrize("path", ["tile", "legacy"])
@pytest.mark.parametrize("func", ["rate", "increase"])
def test_counter_functions_agree_with_prometheus(loaded, func, path, series):
    db, samples = loaded
    dispatched, ineligible = m.TQL_TILE_DISPATCHES.get(), m.TQL_TILE_INELIGIBLE.get()
    db.config.tql.tile = path == "tile"
    try:
        got = db.sql_one(_query(func, series))
    finally:
        db.config.tql.tile = True
    assert m.TQL_TILE_DISPATCHES.get() - dispatched == (path == "tile")
    assert m.TQL_TILE_INELIGIBLE.get() == ineligible

    steps = T0 + np.arange(START_S, END_S + 1, STEP_S, dtype=np.int64) * 1000
    want = promql_ref.extrapolated(
        Fleet, samples[series][:, None], steps, RANGE_S * 1000, func == "rate"
    )[0]
    assert not np.isnan(want).any()
    assert got["host"].to_pylist() == [series] * len(steps)
    assert got["ts"].cast(pa.int64()).to_pylist() == steps.tolist()
    np.testing.assert_allclose(got["value"].to_numpy(), want, rtol=1e-12, atol=0.0)


def test_reference_on_a_window_worked_by_hand():
    """tests/cases/standalone/tql_tile.sql's series `a` at t = 90 s: the
    samples of (30 s, 90 s] are 2, 8, 11, 16 after a restart from 20, so the
    increase is 14 over 45 s, the start lies 15 s before the first sample
    and the zero point 45 x 2 / 14 = 6.43 s before it: 14 x 51.43 / 45 = 16
    (reset-adjusted, the first sample reads 22 and nothing is clamped:
    14 x 60 / 45 = 18.67, the golden's old line)."""

    class Grid:
        t0, scrape_s, ticks = 0, 15, 7

        @staticmethod
        def tick_ts():
            return np.arange(7, dtype=np.int64) * 15_000

    a = np.array([10.0, 14.0, 20.0, 2.0, 8.0, 11.0, 16.0])[:, None]
    steps = np.array([30_000, 60_000, 90_000])
    increase = promql_ref.extrapolated(Grid, a, steps, 60_000, False)[0]
    np.testing.assert_allclose(increase, [12.5, 14.0 * 60.0 / 45.0, 16.0], rtol=1e-15)
    rate = promql_ref.extrapolated(Grid, a, steps, 60_000, True)[0]
    np.testing.assert_allclose(rate, increase / 60.0, rtol=1e-15)
    # fewer than two samples in (t - range, t]: no point
    assert np.isnan(promql_ref.extrapolated(Grid, a, np.array([10_000]), 20_000, True)).all()


@pytest.mark.parametrize("seconds,ms", [
    ("1767225600.123", 1767225600123),
    ("543346458.703", 543346458703),  # x 1000 is ...702.9999 in binary
    ("60", 60000),
])
def test_tql_eval_takes_seconds_to_the_nearest_millisecond(loaded, seconds, ms):
    db, _ = loaded
    got = db.sql_one(f"TQL EVAL ({seconds}, {seconds}, '60s') vector(1)")
    assert got["ts"].cast(pa.int64()).to_pylist() == [ms]
