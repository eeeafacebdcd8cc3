"""A counter of `utils/metrics.py` over SET-UP: the process's total, read
through `program.counters()`, less the window's delta in `run["counters"]`;
times `times`, and with `rows_per` per that many loaded rows.  Nothing where
the program has no such counter.

What the process does once the window has closed (the database's close)
lands in the total as well, so on the set-up side.  A `benchmark` PR may
swap the source for a snapshot `run.py` takes at the end of set-up."""

from benchmark import program


def read(run: dict, counter: str, times: float = 1.0, rows_per: float = 0.0):
    total = program.counters().get(counter)
    if total is None:
        return None
    value = (total - run["counters"].get(counter, 0.0)) * times
    if not rows_per:
        return value
    rows = run["clock"]["rows"]
    return value * rows_per / rows if rows else None
