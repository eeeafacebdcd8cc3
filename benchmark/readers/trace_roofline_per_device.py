"""`trace_roofline` for a cell whose table is sharded over the device
planes: each chip's least time is its share of the traced requests' bytes,
and `busy_s` is already the mean over the device planes."""


def read(run: dict):
    trace = run["trace"]
    if not trace or trace["busy_s"] <= 0 or not trace.get("devices") or not run["traced_least_s"]:
        return None
    return 100.0 * run["traced_least_s"] / trace["devices"] / trace["busy_s"]
