"""Share of the decode rows that carried a live request in the window (%).

100 × Δ``active_slot_steps`` ÷ Δ``slot_steps`` of the program's
``EngineStats``: over the decode steps, the slots holding a request against
all slots.  A closed loop at saturation keeps every slot full; less means
the scheduler left slots empty while requests waited.  None where the
program keeps no such counters or ran no decode step."""


def read(record):
    st = record.stats
    if not st.get("slot_steps") or "active_slot_steps" not in st:
        return None
    return 100.0 * st["active_slot_steps"] / st["slot_steps"]
