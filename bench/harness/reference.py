"""The plain reference's driver: a family's float32 forward, then the LM head.

A model family (``bench/families/<family>.py``, named by the configuration
file) supplies ``forward_rows(params, hf, tokens, rows, low)``: its decoder
written from the published architecture and the configuration file's
published keys, embedding to final-normed hidden rows.  This module runs it
and does what every family shares: grouping the sequences, bucketing and
padding them, the LM head blocked over the vocabulary, and the controls.
Nothing here imports the program; the weights are the benchmark's own, read
by their names in the weight tree.  The helpers a family builds its layers
from (:func:`mm`, :func:`rms`, :func:`rope`, ``Q_BLOCK``) live here.

It runs once the window has closed and the program's state is freed, one
layer at a time over small groups of whole sequences (prompt + served
tokens), with attention computed in blocks of ``Q_BLOCK`` query rows, so
that it fits beside the weights.

:func:`logit_gaps` gives, at every served position, the gap by which the
served token's logit lies below the reference's best.  With
``controls`` it also runs each control: the same reference with every
matrix product (projections and LM head) in a precision below the
configuration's bfloat16 — int8 or fp8 (e4m3), weights scaled per output
channel, activations per row, exact accumulation — and reads the
reference's gap of the token the control puts first.
"""
from __future__ import annotations

import functools
from typing import Callable, Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256          # query rows per attention block
V_BLOCK = 16384        # vocabulary columns per LM-head block


def mm(x, w, low):
    """``x @ w`` in float32 at the highest precision, or with both operands
    in ``low`` ("int8" or "fp8": symmetric, scaled per row of ``x`` and per
    column of ``w``, accumulated exactly)."""
    if low is None:
        return jnp.matmul(x, w, precision=HIGHEST)
    top, dt, acc = LOW[low]
    sx = jnp.maximum(jnp.max(jnp.abs(x), axis=-1, keepdims=True), 1e-12) / top
    sw = jnp.maximum(jnp.max(jnp.abs(w), axis=0, keepdims=True), 1e-12) / top
    xq, wq = _cast(x / sx, top, dt), _cast(w / sw, top, dt)
    y = jax.lax.dot_general(xq, wq, (((x.ndim - 1,), (0,)), ((), ())),
                            preferred_element_type=acc)
    return y.astype(jnp.float32) * sx * sw


def _cast(x, top, dt):
    if dt == jnp.int8:
        return jnp.clip(jnp.round(x), -top, top).astype(dt)
    return jnp.clip(x, -top, top).astype(dt)


# the precisions below the configuration's bfloat16: (largest code, type,
# accumulator)
LOW = {"int8": (127.0, jnp.int8, jnp.int32),
       "fp8": (448.0, jnp.float8_e4m3fn, jnp.float32)}


def rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, pos, theta, rot):
    """Half-split rotary embedding on the first ``rot`` dims of each head."""
    xr, xp = x[..., :rot], x[..., rot:]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[..., None, None].astype(jnp.float32) * inv       # [n,T,1,rot/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(xr, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, xp], -1)


def _head(params, hf):
    if hf.get("tie_word_embeddings"):
        return params["embed"], True          # [V, d]: column j is row j
    return params["lm_head"], False           # [d, V]


def _bucket(n: int, least: int) -> int:
    """The next power of two at or above ``n`` (at least ``least``), so that
    runs share a few compiled shapes."""
    return max(least, 1 << (max(n, 1) - 1).bit_length())


def logit_gaps(forward_rows: Callable, params, hf: Dict,
               seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
               controls: Sequence[str] = (), batch: int = 4
               ) -> Dict[str, np.ndarray]:
    """Gaps at every served position of ``seqs`` ([(prompt, served)]), the
    final rows from the family's ``forward_rows``.

    Returns ``gap`` (reference best minus the served token's reference
    logit, one per served token, in order) and, for each precision in
    ``controls`` ("int8", "fp8"), ``control_gap/<precision>``: reference
    best minus the reference logit of that control's first choice.
    The decoder runs over groups of at most ``batch`` sequences of similar
    length, each group padded to a power-of-two count and length (padding
    is causal-invisible and sliced away), so that the activations of one
    group fit beside the weights."""
    order = sorted(range(len(seqs)),
                   key=lambda i: len(seqs[i][0]) + len(seqs[i][1]))
    pos = {}                 # sequence -> its served rows' place in the output
    finals = {low: [] for low in (None, *controls)}
    served: List[int] = []
    for g0 in range(0, len(order), batch):
        group = order[g0:g0 + batch]
        T = _bucket(max(len(seqs[i][0]) + len(seqs[i][1]) - 1 for i in group),
                    Q_BLOCK)
        tokens = np.zeros((_bucket(len(group), 1), T), np.int32)
        rows: List[int] = []
        for j, i in enumerate(group):
            p, s = seqs[i]
            full = np.concatenate([np.asarray(p, np.int32),
                                   np.asarray(s, np.int32)[:-1]])
            tokens[j, :len(full)] = full
            pos[i] = (len(served), len(s))
            rows.extend(j * T + len(p) - 1 + np.arange(len(s)))
            served.extend(int(t) for t in s)
        R = len(rows)
        rows = jnp.asarray(np.asarray(rows + [0] * (_bucket(R, Q_BLOCK) - R),
                                      np.int32))
        for low in finals:
            finals[low].append(
                forward_rows(params, hf, jnp.asarray(tokens), rows, low)[:R])
    R = len(served)
    pad = _bucket(R, Q_BLOCK) - R
    back = np.concatenate([np.arange(*_span(pos[i])) for i in range(len(seqs))])
    served = jnp.asarray(np.asarray(served + [0] * pad, np.int32))
    head, by_row = _head(params, hf)
    x = _pad_rows(jnp.concatenate(finals[None]), pad)
    gap, best = _scan_head(x, None, head, by_row, served, None)
    out = {"gap": np.asarray(gap)[:R][back]}
    for low in controls:
        xc = _pad_rows(jnp.concatenate(finals[low]), pad)
        _, carg = _scan_head(x, xc, head, by_row, served, low)
        cw = _head_cols(head, by_row, carg)                  # [d, R]
        at = jnp.einsum("rd,dr->r", x, cw, precision=HIGHEST)
        out["control_gap/" + low] = np.asarray(best - at)[:R][back]
    return out


def _span(p):
    return p[0], p[0] + p[1]


def _pad_rows(x, pad):
    return jnp.concatenate([x, jnp.zeros((pad, x.shape[1]), x.dtype)])


def _scan_head(x, xc, head, by_row: bool, served, low):
    """Blocks over the vocabulary.  Without ``xc``: the reference's gap at
    the served tokens and its best logit.  With ``xc`` (a control's final
    rows): the control's first choice, its head in ``low`` as well."""
    V = head.shape[0] if by_row else head.shape[1]
    R = x.shape[0]
    best = jnp.full((R,), -jnp.inf)
    arg = jnp.zeros((R,), jnp.int32)
    at_served = jnp.zeros((R,))
    for v0 in range(0, V, V_BLOCK):
        wb = _head_block(head, by_row, v0, min(V, v0 + V_BLOCK))
        best, arg, at_served = _head_step(x if xc is None else xc, wb, served,
                                          v0, best, arg, at_served, low=low)
    return (best - at_served, best) if xc is None else (None, arg)


def _head_block(head, by_row, v0, v1):
    blk = head[v0:v1].T if by_row else head[:, v0:v1]
    return blk.astype(jnp.float32)


def _head_cols(head, by_row, idx):
    cols = jnp.take(head, idx, axis=0).T if by_row else jnp.take(head, idx, 1)
    return cols.astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("low",))
def _head_step(x, wb, served, v0, best, arg, at_served, low):
    logits = mm(x, wb, low)                                  # [R, Vb]
    bmax, bidx = logits.max(-1), logits.argmax(-1).astype(jnp.int32) + v0
    arg = jnp.where(bmax > best, bidx, arg)
    best = jnp.maximum(best, bmax)
    local = served - v0
    inside = (local >= 0) & (local < wb.shape[1])
    hit = jnp.take_along_axis(
        logits, jnp.clip(local, 0, wb.shape[1] - 1)[:, None], axis=1)[:, 0]
    return best, arg, jnp.where(inside, hit, at_served)
