"""Readings shared by more than one per-layer metric (one file per metric
under ``bench/layer_metrics`` imports these)."""
from __future__ import annotations

from harness.endtoend import in_window

# the compiled Pallas paged-attention kernel's ops in the device trace
PAGED_ATTN = r"^paged_attention"
# the program's dispatch spans, each one step ending in its host sync
STEP_SPANS = ("decode", "mixed", "horizon")


def device_idle(record):
    t = record.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)


def _window_tokens(record):
    """(request, token index) of every token received in the window."""
    return [(r, k) for r in record.recs for k, t in enumerate(r.times)
            if in_window(t, record.window)]


def _useful_flops(record):
    """Model operations of the tokens received in the window: a prompt when
    its first token arrives (every row at its context, the head once), each
    later token as one row at its context with the head."""
    f, hf = record.flops, record.hf
    total = 0.0
    for r, k in _window_tokens(record):
        if k == 0:
            total += f.prompt_flops(hf, r.prompt_len)
        else:
            total += f.token_flops(hf, r.prompt_len + k - 1, True)
    return total


def step_mfu(record):
    """Useful operations over the whole window and the bf16 peak (%)."""
    total = _useful_flops(record)
    if not total or record.peaks is None:
        return None
    return 100.0 * total / record.window.seconds / record.peaks["bf16_flops"]


def step_mfu_in_steps(record):
    """Useful operations over the time the program's dispatches took (the
    window's ``decode``, ``mixed`` and ``horizon`` spans) and the bf16 peak
    (%): the share of the peak while a step is in flight, which an
    open-loop cell's offered load does not fix."""
    busy = sum(s.dur for s in record.spans if s.name in STEP_SPANS)
    total = _useful_flops(record)
    if not total or not busy or record.peaks is None:
        return None
    return 100.0 * total / busy / record.peaks["bf16_flops"]


def paged_attn_roofline(record):
    t = record.trace
    if t is None or record.peaks is None:
        return None
    kernel_s = t.kernel_seconds(PAGED_ATTN)
    if kernel_s <= 0:
        return None
    f, hf = record.flops, record.hf
    ops = nbytes = 0.0
    for r, k in _window_tokens(record):
        if k:                       # token k came from a decode row whose
            kv = r.prompt_len + k   # query sits at position prompt_len+k-1
            ops += f.paged_attn_flops(hf, kv)
            nbytes += f.paged_attn_bytes(hf, kv)
    if not ops:
        return None
    p = record.peaks
    least = max(ops / p["bf16_flops"], nbytes / p["hbm_bytes_per_s"])
    return 100.0 * least / kernel_s
