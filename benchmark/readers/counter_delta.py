"""A counter of `utils/metrics.py`, as its delta over the window; with
`per_request` divided by the window's requests, times `times`."""


def read(run: dict, counter: str, per_request: bool = False, times: float = 1.0):
    if counter not in run["counters"]:
        return None
    value = run["counters"][counter]
    if per_request:
        if not run["requests"]:
            return None
        value /= run["requests"]
    return value * times
