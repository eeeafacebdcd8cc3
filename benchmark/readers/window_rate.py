"""Requests of the window completed correctly over the window's whole
seconds, from the client's side."""


def read(run: dict):
    return run["ok"] / run["elapsed_s"] if run["ok"] else None
