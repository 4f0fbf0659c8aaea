"""Share of the expert rows the MoE layers computed that carried a routed
token, in the window (%).

100 × Δ``moe_held`` ÷ Δ``moe_rows`` of the program's ``EngineStats``:
``moe_held`` is the device's count of the real rows' top-k assignments that
landed on an expert this chip holds, ``moe_rows`` the rows the held experts
computed (every held expert runs the dispatch's whole row count, drop-free,
in every MoE layer).  With capacity equal to the rows on every expert it
reads about top_k ÷ router width; a dispatch that computes only the routed
rows reads more.  Also logs the three counters of the window on standard
error.  None where the program keeps no such counters or computed no
expert row."""
import sys


def read(record):
    st = record.stats
    if not st.get("moe_rows") or "moe_held" not in st:
        return None
    print(f"[bench] moe in the window: moe_routed {st.get('moe_routed', 0)}, "
          f"moe_held {st['moe_held']}, moe_rows {st['moe_rows']}",
          file=sys.stderr, flush=True)
    return 100.0 * st["moe_held"] / st["moe_rows"]
