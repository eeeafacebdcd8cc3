"""jax's `backend_compile_duration` events of one phase (`setup` or
`window`): their summed `seconds` or their `count`."""


def read(run: dict, phase: str, what: str):
    events = run["compiles"][phase]
    return float(sum(events)) if what == "seconds" else float(len(events))
