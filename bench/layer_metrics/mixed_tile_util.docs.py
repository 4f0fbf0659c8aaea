"""Share of the rows the mixed program ran that carry a real token (%): over
the window, the program's ``EngineStats`` counters (``serving/metrics.py``),
100 × Δ(``mixed_decode_rows`` + ``mixed_prefill_rows``) ÷ Δ``mixed_tile_rows``
— the rows of the decode group and the prefill lanes, ``slots + lanes·Q`` a
dispatch.  None where the program keeps no ``mixed_tile_rows`` counter."""

ROWS = ("mixed_decode_rows", "mixed_prefill_rows")


def read(record):
    st = record.stats
    if not st.get("mixed_tile_rows") or any(k not in st for k in ROWS):
        return None
    return 100.0 * sum(st[k] for k in ROWS) / st["mixed_tile_rows"]
