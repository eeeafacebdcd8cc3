"""The part of a request's time that passes outside the server's own
`http.request` stage: the mean of the window's latencies, as the client
took them, minus the program's inclusive request seconds (`counter`) per
request.  It holds the client's send, the socket, and the harness's own
parse of the answer.  Nothing where the program has no such counter."""


def read(run: dict, counter: str):
    if counter not in run["counters"] or not run["requests"]:
        return None
    latencies = run["latencies_ms"]
    inside_ms = run["counters"][counter] * 1000.0 / run["requests"]
    return sum(latencies) / len(latencies) - inside_ms
