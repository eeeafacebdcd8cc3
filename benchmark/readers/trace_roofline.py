"""The least time the chip could take for the traced requests' bytes
(`roofline.py`) over the device's busy time in the traced slice: device
busy time, not a named kernel's events, so the share reads the same work
whatever implements it."""


def read(run: dict):
    trace = run["trace"]
    if not trace or trace["busy_s"] <= 0 or not run["traced_least_s"]:
        return None
    return 100.0 * run["traced_least_s"] / trace["busy_s"]
