"""Seconds the harness took on the host's clock around one or more set-up
phases; with `rows_per`, the loaded rows per `rows_per` over those seconds."""


def read(run: dict, seconds: list, rows_per: float = 0.0):
    total = sum(run["clock"][name] for name in seconds)
    if not rows_per:
        return total
    return run["clock"]["rows"] / rows_per / total if total > 0 else None
