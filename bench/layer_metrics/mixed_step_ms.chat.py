"""Mean wall time of the program's ``mixed`` dispatch spans in the window (ms).

Each span is the host clock around one fused prefill+decode dispatch,
ending in its host sync (``serving/engine.py`` ``_dispatch_mixed``)."""


def read(record):
    d = [s.dur for s in record.spans if s.name == "mixed"]
    return 1e3 * sum(d) / len(d) if d else None
