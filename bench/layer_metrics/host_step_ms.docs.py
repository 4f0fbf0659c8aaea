"""Host time per engine step outside the compiled call and its sync (ms).

Over the window, the program's ``EngineStats`` phase counters
(``serving/engine.py``): Δ(``host_plan_s`` + ``host_pack_s`` +
``host_tables_s`` + ``host_wear_s`` + ``host_emit_s``) ÷ Δ``steps`` — the
scheduler, input packing, block-table upload, endurance mirror and token
emission the host runs while the device waits for its next step.  None where
the program keeps no such counters."""

PHASES = ("host_plan_s", "host_pack_s", "host_tables_s", "host_wear_s",
          "host_emit_s")


def read(record):
    st = record.stats
    if not st.get("steps") or any(k not in st for k in PHASES):
        return None
    return 1e3 * sum(st[k] for k in PHASES) / st["steps"]
