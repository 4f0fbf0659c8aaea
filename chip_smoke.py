#!/usr/bin/env python3
"""Chip smoke: serve phi4-mini-3.8b on one TPU through the real entry points.

A smoke, not a benchmark: it proves the serving main path starts and gives
right answers on the chip — FrontDoor → ServingEngine → paged mixed/decode
dispatch → compiled Pallas paged attention — at the model's published
widths, with random weights drawn from ``--seed``.  One process, phases in
order; any failed phase raises and the script exits non-zero:

  a. compile cache  ``$JAX_COMPILATION_CACHE_DIR``, else ``<repo>/.jax_cache``
  b. device check   the platform must be ``tpu`` (no CPU fallback)
  c. kernel         compiled ``paged_attention`` vs ``paged_attn_ref`` at
                    phi4 decode shapes: Q=1 and Q=5, bf16 and int8 pools
  d. serve          8 requests through ``FrontDoor.submit``, prompts spread
                    over 128..1536 tokens (chunked prefill at 512 + mixed
                    dispatch), 32 greedy tokens each
  e. reference      one uncached ``lm.forward`` at highest matmul precision
                    over one request; every emitted token whose reference
                    top-1/top-2 margin exceeds ``MARGIN`` must be its argmax
  f. report         compile seconds, serve wall, tokens, peak device bytes

The last line of stdout is ``{"ok": true, "device": {...}}``.

Usage:  python chip_smoke.py [--seed 0]
"""
from __future__ import annotations

import argparse
import asyncio
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(ROOT, "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.paged_attn import paged_attention  # noqa: E402
from repro.launch.compile_cache import use_compile_cache  # noqa: E402
from repro.models import lm, registry  # noqa: E402
from repro.nn import module as nnmod  # noqa: E402
from repro.nn.attention import KV_SCALE, _cache_write  # noqa: E402
from repro.nn.layers import rmsnorm  # noqa: E402
from repro.serving import FrontDoor, Request, ServingEngine  # noqa: E402

ARCH = "phi4-mini-3.8b"
SLOTS, MAX_LEN, BLOCK_SIZE, CHUNK = 8, 2048, 16, 512
PROMPT_LENS = tuple(int(n) for n in np.linspace(128, 1536, SLOTS))
GEN = 32
# kernel vs oracle: max abs error over the reference's largest magnitude
# (the kernel emits bf16, ~2^-9 relative, and dots on the MXU)
KERNEL_RTOL = 1e-2
# reference top-1/top-2 logit margin above which the served token must be
# the reference argmax; the served path keeps bf16 logits (spacing 2^-6 at
# magnitude 4) and bf16 attention probabilities, so closer calls may flip
MARGIN = 0.15
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class SmokeFailure(RuntimeError):
    pass


def require(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def device_info() -> dict:
    d = jax.devices()[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(jax.devices())}


def peak_bytes():
    stats = jax.devices()[0].memory_stats() or {}
    return stats.get("peak_bytes_in_use", "not reported")


def track_compiles() -> dict:
    """Sum backend compile seconds (cache retrieval included) and count
    persistent-cache hits for the rest of the process."""
    seen = {"compile_s": 0.0, "cache_hits": 0}

    def on_duration(event, duration_secs, **_):
        if event == COMPILE_EVENT:
            seen["compile_s"] += duration_secs

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            seen["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    return seen


# ---------------------------------------------------------------- c. kernel

def kernel_phase(cfg, seed: int) -> None:
    """Compiled kernel vs the jnp oracle at the model's decode shapes."""
    attn = cfg.blocks[0].attn
    B, H, Hkv, D = SLOTS, attn.n_heads, attn.n_kv_heads, attn.d_head
    P = MAX_LEN // BLOCK_SIZE
    N = B * P + 1
    rng = np.random.default_rng(seed)
    for Q in (1, 5):
        for int8 in (False, True):
            shape = (N, BLOCK_SIZE, Hkv, D)
            kp = jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)
            vp = jnp.asarray(rng.normal(size=shape) * 0.5, jnp.float32)
            cdt = jnp.int8 if int8 else jnp.bfloat16
            kp, vp = _cache_write(kp, cdt), _cache_write(vp, cdt)
            qs = (B, H, D) if Q == 1 else (B, Q, H, D)
            q = jnp.asarray(rng.normal(size=qs) * 0.5, jnp.bfloat16)
            tables = jnp.asarray(rng.permutation(N)[:B * P].reshape(B, P),
                                 jnp.int32)
            lengths = jnp.asarray(rng.integers(Q, P * BLOCK_SIZE + 1, B),
                                  jnp.int32)
            kv_scale = KV_SCALE if int8 else None
            out = paged_attention(q, kp, vp, tables, lengths,
                                  kv_scale=kv_scale)
            ref = paged_attention(q.astype(jnp.float32), kp, vp, tables,
                                  lengths, kv_scale=kv_scale,
                                  use_kernel=False)
            out = np.asarray(out.astype(jnp.float32))
            ref = np.asarray(ref)
            err = float(np.abs(out - ref).max())
            bound = KERNEL_RTOL * float(np.abs(ref).max())
            name = f"Q={Q} {'int8' if int8 else 'bf16'}"
            print(f"[smoke] kernel {name}: max abs err {err:.3e} "
                  f"(bound {bound:.3e})", flush=True)
            require(np.isfinite(out).all(), f"kernel {name}: non-finite output")
            require(err <= bound, f"kernel {name}: error {err} > {bound}")


# ---------------------------------------------------------------- d. serve

async def _collect(stream):
    toks, done = [], None
    async for ev in stream:
        if ev.kind == "token":
            toks.append(ev.token[0])
        elif ev.kind == "done":
            done = ev
    return toks, done


def serve_phase(cfg, params, *, prompt_lens, gen: int, slots: int,
                max_len: int, block_size: int, chunk: int, seed: int):
    """Serve one request per prompt length through the front door and check
    every stream.  Returns (engine, requests, token lists, wall seconds)."""
    engine = ServingEngine(cfg, slots=slots, max_len=max_len,
                           block_size=block_size, prefill_chunk=chunk,
                           params=params)
    rng = np.random.default_rng(seed)
    reqs = [Request(rid=i, prompt=rng.integers(0, cfg.vocab, n, np.int32),
                    max_new=gen) for i, n in enumerate(prompt_lens)]

    async def drive():
        fd = FrontDoor(engine, max_queue=len(reqs))
        await fd.start()
        outs = await asyncio.gather(*[_collect(fd.submit(r)) for r in reqs])
        await fd.shutdown()
        return outs

    t0 = time.perf_counter()
    outs = asyncio.run(drive())
    wall = time.perf_counter() - t0
    for r, (toks, done) in zip(reqs, outs):
        require(done is not None and done.state == "done",
                f"rid {r.rid}: ended {done}")
        require(len(toks) == gen == done.n_tokens,
                f"rid {r.rid}: {len(toks)} tokens, wanted {gen}")
        require(all(0 <= t < cfg.vocab for t in toks),
                f"rid {r.rid}: token id out of [0, {cfg.vocab})")
    st = engine.stats
    require(st.mixed_dispatches > 0 and st.mixed_decode_rows > 0,
            "no mixed dispatch carried decode rows")
    require(max(prompt_lens) > chunk, "no prompt spans two prefill chunks")
    require(st.decode_dispatches > 0, "no decode dispatch ran")
    finite = all(bool(jnp.isfinite(leaf).all())
                 for leaf in jax.tree.leaves(engine.caches)
                 if jnp.issubdtype(leaf.dtype, jnp.floating))
    require(finite, "non-finite values in the KV pool")
    return engine, reqs, [t for t, _ in outs], wall


# ---------------------------------------------------------------- e. reference

def reference_phase(cfg, params, prompt, tokens, margin: float) -> dict:
    """Teacher-forced uncached forward over prompt + tokens[:-1]; row j
    predicts ``tokens[j]``.  Tokens whose reference top-1/top-2 margin
    exceeds ``margin`` must equal the reference argmax."""
    n = len(prompt)
    seq = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(tokens[:-1], np.int32)])[None]

    def logits_fn(params, seq):
        _, _, hidden = lm.forward(params, seq, cfg)
        x = rmsnorm(hidden[0, n - 1:].astype(jnp.float32),
                    params["final_norm"], cfg.norm_eps)
        head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
        return jnp.matmul(x, head.astype(jnp.float32))

    with jax.default_matmul_precision("highest"):
        logits = np.asarray(jax.jit(logits_fn)(params, jnp.asarray(seq)))
    require(np.isfinite(logits).all(), "non-finite reference logits")
    top2 = np.sort(logits, axis=-1)[:, -2:]
    margins = top2[:, 1] - top2[:, 0]
    ref_tok = logits.argmax(axis=-1)
    got = np.asarray(tokens)
    checked = margins > margin
    bad = checked & (ref_tok != got)
    out = {"tokens": len(got), "checked": int(checked.sum()),
           "agree_all": int((ref_tok == got).sum()),
           "mismatches_checked": int(bad.sum()),
           "min_checked_margin": (float(margins[checked].min())
                                  if checked.any() else None),
           "max_unchecked_margin": (float(margins[~checked].max())
                                    if (~checked).any() else None)}
    print(f"[smoke] reference: {out}", flush=True)
    require(not bad.any(),
            f"tokens {np.flatnonzero(bad).tolist()} differ from the reference "
            f"argmax at margins {margins[bad].tolist()} > {margin}")
    return out


# ---------------------------------------------------------------- main

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cache_dir = use_compile_cache()                                    # a
    compiles = track_compiles()
    info = device_info()                                               # b
    print(f"[smoke] device platform={info['platform']} "
          f"kind={info['kind']} count={info['count']}", flush=True)
    if info["platform"] != "tpu":
        print(json.dumps({"ok": False, "device": info,
                          "error": "no TPU found; this smoke runs on the chip "
                                   "only"}))
        return 1
    print(f"[smoke] compile cache: {cache_dir}", flush=True)

    cfg = registry.get_config(ARCH)
    t0 = time.perf_counter()
    kernel_phase(cfg, args.seed)                                       # c
    print(f"[smoke] kernel phase {time.perf_counter() - t0:.1f} s",
          flush=True)

    t0, c0 = time.perf_counter(), compiles["compile_s"]
    params = nnmod.materialize(lm.param_spec(cfg),
                               jax.random.PRNGKey(args.seed))
    jax.block_until_ready(params)
    print(f"[smoke] {ARCH}: d_model {cfg.d_model}, {cfg.n_layers} layers, "
          f"vocab {cfg.vocab}, {nnmod.count_params(lm.param_spec(cfg)):,} "
          f"params; random init {time.perf_counter() - t0:.1f} s (compile "
          f"{compiles['compile_s'] - c0:.1f} s of it); peak_bytes_in_use "
          f"{peak_bytes()}", flush=True)

    c0 = compiles["compile_s"]
    engine, reqs, toks, wall = serve_phase(                            # d
        cfg, params, prompt_lens=PROMPT_LENS, gen=GEN, slots=SLOTS,
        max_len=MAX_LEN, block_size=BLOCK_SIZE, chunk=CHUNK, seed=args.seed)
    st = engine.stats
    print(f"[smoke] served {len(reqs)} requests (prompts {list(PROMPT_LENS)}),"
          f" {sum(len(t) for t in toks)} tokens in {wall:.2f} s wall "
          f"(compile {compiles['compile_s'] - c0:.1f} s of it); dispatches: "
          f"mixed {st.mixed_dispatches}, decode {st.decode_dispatches}",
          flush=True)

    i = int(np.argmax(PROMPT_LENS))                                    # e
    t0 = time.perf_counter()
    reference_phase(cfg, params, reqs[i].prompt, toks[i], MARGIN)
    print(f"[smoke] reference phase {time.perf_counter() - t0:.1f} s",
          flush=True)

    # f
    print(f"[smoke] SMOKE, NOT A BENCHMARK: compile {compiles['compile_s']:.1f}"
          f" s total, {compiles['cache_hits']} persistent-cache hits; serve "
          f"wall {wall:.2f} s; peak_bytes_in_use {peak_bytes()}", flush=True)
    print(json.dumps({"ok": True, "device": info}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
