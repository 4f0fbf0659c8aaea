"""Share of the mixed tile's rows that carry a real token (%): over the
window's ``mixed`` spans, Σ(decode_rows + prefill_rows) ÷ Σ(slots · q_tile)."""


def read(record):
    spans = [s for s in record.spans if s.name == "mixed"]
    if not spans:
        return None
    real = sum(s.args["decode_rows"] + s.args["prefill_rows"] for s in spans)
    tile = sum(record.engine["slots"] * s.args["q_tile"] for s in spans)
    return 100.0 * real / tile
