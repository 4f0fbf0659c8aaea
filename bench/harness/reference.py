"""The plain reference: a dense decoder in float32 at the highest precision.

Written from the published architecture (Phi-3 / Phi-4-mini: pre-norm
RMSNorm, rotary embeddings in the half-split form, grouped-query attention,
SwiGLU MLP, residual stream, final RMSNorm, tied or untied LM head) and the
configuration file's published keys.  It imports nothing of the program; it
reads the benchmark's own weights by their names in the weight tree.

It runs once the window has closed and the program's state is freed, one
layer at a time over small groups of whole sequences (prompt + served
tokens), with attention computed in blocks of query rows, so that it fits
beside the weights.

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
from typing import Dict, List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
Q_BLOCK = 256          # query rows per attention block
V_BLOCK = 16384        # vocabulary columns per LM-head block


def _mm(x, w, low):
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


def _rms(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def _rope(x, pos, theta, rot):
    """Half-split rotary embedding on the first ``rot`` dims of each head."""
    xr, xp = x[..., :rot], x[..., rot:]
    inv = 1.0 / theta ** (jnp.arange(0, rot, 2, dtype=jnp.float32) / rot)
    ang = pos[..., None, None].astype(jnp.float32) * inv       # [n,T,1,rot/2]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = jnp.split(xr, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos, xp], -1)


def _dims(hf: Dict) -> Tuple:
    """(heads, kv heads, head size, rotated dims, norm eps, rope theta)."""
    H, Hkv = hf["num_attention_heads"], hf["num_key_value_heads"]
    D = hf.get("head_dim") or hf["hidden_size"] // H
    rot = int(D * hf.get("partial_rotary_factor", 1.0))
    return H, Hkv, D, rot, float(hf["rms_norm_eps"]), float(hf["rope_theta"])


def _layer(x, seg, l, dims, low):
    """One decoder layer on x [n, T, d] (float32), weights of layer ``l``."""
    H, Hkv, D, rot, eps, theta = dims
    w = jax.tree.map(lambda a: a[l].astype(jnp.float32), seg)
    n, T, _ = x.shape
    pos = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32), (n, T))
    h = _rms(x, w["ln1"], eps)
    q = _rope(_mm(h, w["attn"]["q"], low).reshape(n, T, H, D), pos, theta, rot)
    k = _rope(_mm(h, w["attn"]["k"], low).reshape(n, T, Hkv, D), pos, theta, rot)
    v = _mm(h, w["attn"]["v"], low).reshape(n, T, Hkv, D)
    G = H // Hkv
    k = jnp.repeat(k, G, axis=2)             # query head j reads kv head j // G
    v = jnp.repeat(v, G, axis=2)
    kpos = jnp.arange(T)

    def block(i):
        qb = jax.lax.dynamic_slice_in_dim(q, i * Q_BLOCK, Q_BLOCK, axis=1)
        s = jnp.einsum("nqhd,nkhd->nhqk", qb, k, precision=HIGHEST) / np.sqrt(D)
        qpos = i * Q_BLOCK + jnp.arange(Q_BLOCK)
        s = jnp.where(kpos[None, :] <= qpos[:, None], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        return jnp.einsum("nhqk,nkhd->nqhd", p, v, precision=HIGHEST)

    o = jax.lax.map(block, jnp.arange(T // Q_BLOCK))        # [B, n, Qb, H, D]
    o = jnp.moveaxis(o, 0, 1).reshape(n, T, H * D)
    x = x + _mm(o, w["attn"]["o"], low)
    h = _rms(x, w["ln2"], eps)
    m = w["mlp"]
    g = jax.nn.silu(_mm(h, m["w_gate"], low)) * _mm(h, m["w_up"], low)
    return x + _mm(g, m["w_down"], low)


_layer_jit = jax.jit(_layer, static_argnames=("dims", "low"))


def _final_rows(params, hf, tokens, rows, low):
    """Final-normed hidden rows ``rows`` ([R] flat indices) of the batch."""
    x = jnp.take(params["embed"], tokens, axis=0).astype(jnp.float32)
    seg = params["segments"][0]
    dims = _dims(hf)
    for l in range(hf["num_hidden_layers"]):
        x = _layer_jit(x, seg, l, dims=dims, low=low)
    x = x.reshape(-1, x.shape[-1])[rows]
    return _rms(x, params["final_norm"].astype(jnp.float32),
                hf["rms_norm_eps"])


def _head(params, hf):
    if hf.get("tie_word_embeddings"):
        return params["embed"], True          # [V, d]: column j is row j
    return params["lm_head"], False           # [d, V]


def _bucket(n: int, least: int) -> int:
    """The next power of two at or above ``n`` (at least ``least``), so that
    runs share a few compiled shapes."""
    return max(least, 1 << (max(n, 1) - 1).bit_length())


def logit_gaps(params, hf: Dict, seqs: Sequence[Tuple[np.ndarray, np.ndarray]],
               controls: Sequence[str] = (), batch: int = 4
               ) -> Dict[str, np.ndarray]:
    """Gaps at every served position of ``seqs`` ([(prompt, served)]).

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
                _final_rows(params, hf, jnp.asarray(tokens), rows, low)[:R])
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
    logits = _mm(x, wb, low)                                  # [R, Vb]
    bmax, bidx = logits.max(-1), logits.argmax(-1).astype(jnp.int32) + v0
    arg = jnp.where(bmax > best, bidx, arg)
    best = jnp.maximum(best, bmax)
    local = served - v0
    inside = (local >= 0) & (local < wb.shape[1])
    hit = jnp.take_along_axis(
        logits, jnp.clip(local, 0, wb.shape[1] - 1)[:, None], axis=1)[:, 0]
    return best, arg, jnp.where(inside, hit, at_served)
