"""Mean wall time of the program's ``decode`` dispatch spans in the window (ms).

Each span is the host clock around one decode dispatch of every slot,
ending in its host sync (``serving/engine.py``)."""


def read(record):
    d = [s.dur for s in record.spans if s.name == "decode"]
    return 1e3 * sum(d) / len(d) if d else None
