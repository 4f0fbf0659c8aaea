"""Mean front-door delivery lag per token in the window (ms).

Δ``deliver_lag_s`` ÷ Δ``delivered_tokens`` of the program's ``EngineStats``:
for each token, the engine clock when the consumer's stream yields it
(``FrontDoor._consume``) less the token's emission stamp.  None where the
program keeps no such counters or delivered no token."""


def read(record):
    st = record.stats
    if not st.get("delivered_tokens") or "deliver_lag_s" not in st:
        return None
    return 1e3 * st["deliver_lag_s"] / st["delivered_tokens"]
