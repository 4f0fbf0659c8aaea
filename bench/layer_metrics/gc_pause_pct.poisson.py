"""Share of the window the host process spent in garbage collection (%).

100 × Δ``gc_pause_s`` of the program's ``EngineStats`` (every generation,
counted by the tracer's ``gc.callbacks`` hook while it is attached) ÷ the
window.  None where the program keeps no such counter."""


def read(record):
    if "gc_pause_s" not in record.stats:
        return None
    return 100.0 * record.stats["gc_pause_s"] / record.window.seconds
