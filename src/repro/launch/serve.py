"""Serving driver — thin CLI over the continuous-batching engine.

``serve()`` now routes through :class:`repro.serving.ServingEngine`: each
prompt becomes a request, the engine admits them into cache slots, chunked
prefill interleaves with the fixed ``[B, 1]`` decode step, and freed slots
re-admit queued work.  The old one-shot static-batch loop survives as
``serve_static()`` — it is the baseline the serving benchmark beats and the
parity witness the engine tests decode against.

Continuous-batching shape discipline: the serving caches are fixed
``[slots, max_len]`` and generation always runs the same ``[slots, 1]`` step,
so one compiled executable serves every request mix (no recompiles
mid-flight); only distinct prefill chunk lengths trace separately.

Usage:
  PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b --smoke \
      --batch 4 --prompt-len 32 --gen 16
  # mixed-length open-loop workload with a constrained KV pool:
  PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b --smoke \
      --scenario mixed --requests 16 --slots 4 --kv-blocks 20
  # record a dispatch/lifecycle timeline, open trace.json in ui.perfetto.dev:
  PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b --smoke \
      --scenario mixed --trace-out trace.json
  # expose the engine as a streaming HTTP front door (SSE, 429 on overload):
  PYTHONPATH=src python -m repro.launch.serve --arch phi4-mini-3.8b --smoke \
      --listen --port 8080 --max-queue 32 --tenant-rate 50
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ShapeConfig
from repro.launch import specs as specs_mod
from repro.launch.compile_cache import use_compile_cache
from repro.launch.steps import make_decode_step, make_prefill_step
from repro.models import lm, registry
from repro.nn import module as nnmod
from repro.serving import (SCENARIOS, FaultPlan, ReliabilityConfig, Request,
                           ServingEngine, Tracer, make_requests)

__all__ = ["serve", "serve_static", "serve_listen", "main"]


def serve_listen(cfg, *, host: str = "127.0.0.1", port: int = 8080,
                 slots: int = 4, max_len: int = 128, block_size: int = 16,
                 max_queue: int = 64, tenant_rate: float | None = None,
                 tenant_burst: float | None = None,
                 heartbeat_s: float | None = None, params=None,
                 verbose: bool = True, **engine_kwargs):
    """Expose the engine as a streaming HTTP front door.

    ``POST /generate`` with ``{"prompt": [ids]}`` (or ``{"prompt_len": n}``
    for a random prompt) streams token/heartbeat/done events as SSE; an
    overloaded queue or an over-quota tenant gets ``429`` + ``Retry-After``,
    submissions during shutdown get ``503``.  SIGTERM/SIGINT drain
    gracefully: in-flight streams flush, then the engine summary prints.
    Blocks until shutdown; returns the final summary.
    """
    import asyncio

    from repro.serving.frontdoor import FrontDoor, run_server

    engine = ServingEngine(cfg, slots=slots, max_len=max_len,
                           block_size=block_size, params=params,
                           **engine_kwargs)
    fd = FrontDoor(engine, max_queue=max_queue, tenant_rate=tenant_rate,
                   tenant_burst=tenant_burst, heartbeat_s=heartbeat_s)
    if verbose:
        print(f"[serve] front door on http://{host}:{port}/generate  "
              f"(slots={slots}, max_len={max_len}, queue≤{max_queue}"
              + (f", tenant quota {tenant_rate}/s" if tenant_rate else "")
              + ")  SIGTERM drains gracefully")
    try:
        asyncio.run(run_server(fd, host, port, vocab=cfg.vocab))
    except KeyboardInterrupt:
        pass
    summary = engine.summary()
    if verbose:
        print(f"[serve] drained: terminal {summary['terminal']}, "
              f"front door {fd.summary()}")
    return summary


def serve_static(cfg, *, batch: int, prompt_len: int, gen: int, seed: int = 0,
                 params=None, verbose: bool = True):
    """The original static-batch loop: one prefill, ``gen`` lockstep decode
    steps, every slot runs to the end even if its request is done.

    Returns (generated [B, gen] int32, decode tokens/s).
    """
    if params is None:
        params = nnmod.materialize(lm.param_spec(cfg), jax.random.PRNGKey(seed))
    max_len = prompt_len + gen
    shape = ShapeConfig("serve", prompt_len, batch, "prefill")
    batch_data = specs_mod.concrete_batch(cfg, shape, seed, 0)

    prefill = jax.jit(make_prefill_step(cfg, max_len=max_len))
    decode = jax.jit(make_decode_step(cfg), donate_argnums=(1,))

    t0 = time.time()
    last_logits, caches = prefill(params, batch_data)
    if cfg.n_codebooks > 1:
        tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)[:, :, None]  # [B,K,1]
    else:
        tok = jnp.argmax(last_logits, axis=-1).astype(jnp.int32)[:, None]     # [B,1]
    t_prefill = time.time() - t0

    outs = []
    t1 = time.time()
    for _ in range(gen):
        outs.append(tok)
        tok, caches = decode(params, caches, tok)
    jax.block_until_ready(tok)
    t_decode = time.time() - t1

    generated = jnp.concatenate(outs, axis=-1)
    tps = batch * gen / max(t_decode, 1e-9)
    if verbose:
        print(f"[serve] static prefill {batch}×{prompt_len} in {t_prefill*1e3:.0f} ms; "
              f"decode {gen} steps in {t_decode*1e3:.0f} ms  ({tps:.1f} tok/s)")
    return generated, tps


def _batch_requests(cfg, batch: int, prompt_len: int, gen: int, seed: int):
    """The static driver's workload as engine requests: same concrete batch,
    all arriving at t=0."""
    shape = ShapeConfig("serve", prompt_len, batch, "prefill")
    data = specs_mod.concrete_batch(cfg, shape, seed, 0)
    toks = np.asarray(data["tokens"])
    reqs = []
    for i in range(batch):
        extras = None
        if cfg.vision_stub:
            extras = {"patch_embeds": np.asarray(data["patch_embeds"])[i],
                      "pos3d": np.asarray(data["pos3d"])[i]}
        reqs.append(Request(rid=i, prompt=toks[i], max_new=gen, extras=extras))
    return reqs


def serve(cfg, *, batch: int, prompt_len: int, gen: int, seed: int = 0,
          params=None, verbose: bool = True, slots: int | None = None,
          block_size: int | None = None, **engine_kwargs):
    """Serve the static driver's workload through the continuous-batching
    engine.  Returns (generated [B, gen] int32, decode tokens/s) — the same
    contract as ``serve_static`` (token-for-token equal on a fixed seed when
    no preemption occurs; asserted in tests/test_serving.py).
    """
    slots = slots or batch
    max_len = prompt_len + gen
    if block_size is None:
        block_size = next(b for b in (16, 8, 4, 2, 1) if max_len % b == 0)
    engine = ServingEngine(cfg, slots=slots, max_len=max_len,
                           block_size=block_size, params=params, seed=seed,
                           **engine_kwargs)
    reqs = _batch_requests(cfg, batch, prompt_len, gen, seed)
    summary = engine.run(reqs)
    generated = jnp.asarray(
        np.stack([np.stack(r.generated, axis=-1) for r in sorted(reqs, key=lambda r: r.rid)]))
    tps = summary["decode_tokens_per_s"]
    if verbose:
        print(f"[serve] engine {batch} reqs×{prompt_len}+{gen} over {slots} slots: "
              f"prefill {summary['prefill_time_s']*1e3:.0f} ms, "
              f"decode {summary['decode_steps']} steps in "
              f"{summary['decode_time_s']*1e3:.0f} ms  ({tps:.1f} tok/s, "
              f"occupancy {summary['slot_occupancy']:.2f})")
    return generated, tps


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", required=True, choices=registry.ARCH_IDS)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--static", action="store_true",
                    help="run the legacy static-batch loop instead of the engine")
    ap.add_argument("--odin-mode", choices=["exact", "int8", "sc"], default=None,
                    help="execution mode for Linear layers (default: config's)")
    ap.add_argument("--no-paged", action="store_true",
                    help="keep the dense [slots, max_len] live caches instead "
                         "of the paged physical block store")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable shared-prompt block dedup (refcounted "
                         "prefix cache; auto-enabled for fully paged models)")
    ap.add_argument("--no-mixed", action="store_true",
                    help="disable the fused mixed prefill+decode dispatch "
                         "(token-budget packed tiles; auto-enabled for fully "
                         "paged models) and fall back to separate prefill "
                         "and decode launches")
    ap.add_argument("--mixed-budget", type=int, default=None,
                    help="total query-row budget of one mixed dispatch "
                         "(default: prefill chunk + slots)")
    ap.add_argument("--horizon", type=int, default=1,
                    help="max decode steps fused into one dispatch (power-of-"
                         "two grants; 1 = per-token parity baseline)")
    ap.add_argument("--spec-ngram", type=int, default=0, metavar="K",
                    help="n-gram self-speculative decode: draft K tokens per "
                         "inner step by prompt-lookup and verify them in one "
                         "multi-token forward (greedy only; 0 = off)")
    ap.add_argument("--eos-id", type=int, default=None,
                    help="token id that ends a request early (default: none)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy argmax)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="top-k truncation for sampling (0 = full vocab)")
    ap.add_argument("--sample-seed", type=int, default=0)
    # open-loop scenario mode (ignores --batch/--prompt-len/--gen)
    ap.add_argument("--scenario", choices=sorted(SCENARIOS), default=None,
                    help="serve a synthetic open-loop workload instead")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--kv-blocks", type=int, default=None,
                    help="device KV budget in blocks (forces preemption when low)")
    ap.add_argument("--swap-blocks", type=int, default=0)
    ap.add_argument("--block-size", type=int, default=None,
                    help="KV block granularity (default: 16 for scenarios, "
                         "auto-picked to divide prompt+gen otherwise)")
    ap.add_argument("--chunk", type=int, default=None, help="prefill chunk length")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="record a structured event trace and write it as "
                         "Chrome trace-event JSON (open in ui.perfetto.dev)")
    ap.add_argument("--trace-capacity", type=int, default=65536,
                    help="trace ring-buffer capacity (oldest events drop "
                         "beyond it; drops are counted in the file)")
    ap.add_argument("--metrics-window", type=float, default=1.0,
                    help="windowed-metrics snapshot period in seconds")
    ap.add_argument("--xla-annotations", action="store_true",
                    help="with --trace-out, also open a jax.profiler "
                         "TraceAnnotation serving/<phase> for each host "
                         "phase (aligns XLA profiles with spans)")
    ap.add_argument("--deadline-ms", type=float, default=None,
                    help="default per-request deadline after arrival; past-"
                         "deadline requests finish as TIMEOUT (slot freed "
                         "mid-run, KV blocks released)")
    ap.add_argument("--queue-timeout-ms", type=float, default=None,
                    help="max queue wait before admission; expired waiters "
                         "finish as TIMEOUT without ever running")
    ap.add_argument("--degrade", action="store_true",
                    help="enable the graceful-degradation ladder (spec off → "
                         "horizon shrink → prefix release → admission denial)")
    ap.add_argument("--reliability", action="store_true",
                    help="enable the PCRAM reliability layer with defaults "
                         "(wear-leveled allocation; no endurance budget, no "
                         "scrub unless the flags below say so)")
    ap.add_argument("--endurance-budget", type=int, default=None,
                    help="per-block PCRAM write budget in cache rows; a block "
                         "crossing it is drained (contents copied, tables "
                         "remapped) and retired (implies --reliability)")
    ap.add_argument("--no-wear-leveling", action="store_true",
                    help="keep the seed LIFO free-list order instead of "
                         "min-wear allocation (only meaningful with the "
                         "reliability layer on)")
    ap.add_argument("--scrub-rate", type=int, default=0, metavar="N",
                    help="drift-refresh scrubber: rewrite up to N oldest-"
                         "written resident blocks per step once past the "
                         "drift deadline (implies --reliability; needs "
                         "--drift-deadline-ms)")
    ap.add_argument("--drift-deadline-ms", type=float, default=None,
                    help="resistance-drift deadline: a resident block older "
                         "than this since its last write is due for a scrub "
                         "rewrite (implies --reliability)")
    ap.add_argument("--fault-plan", default=None, metavar="PATH",
                    help="seeded fault-injection plan (JSON, see repro.serving"
                         ".faults.FaultPlan); scenario mode only — faults are "
                         "a test instrument, not a serving feature")
    # streaming front-door mode (ignores --batch/--scenario; clients bring
    # their own prompts over HTTP)
    ap.add_argument("--listen", action="store_true",
                    help="serve POST /generate as an SSE token stream through "
                         "the asyncio front door (429 + Retry-After on "
                         "overload, 503 while draining, SIGTERM drains)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=8080)
    ap.add_argument("--max-queue", type=int, default=64,
                    help="waiting-queue bound before typed 429 rejection")
    ap.add_argument("--max-len", type=int, default=None,
                    help="per-request prompt+gen cap for --listen "
                         "(default: --prompt-len + --gen)")
    ap.add_argument("--tenant-rate", type=float, default=None,
                    help="per-tenant emitted-token quota (tokens/s; off by "
                         "default)")
    ap.add_argument("--tenant-burst", type=float, default=None,
                    help="per-tenant bucket burst (default: --tenant-rate)")
    ap.add_argument("--heartbeat-ms", type=float, default=None,
                    help="idle-stream heartbeat period")
    args = ap.parse_args()
    use_compile_cache()
    if args.fault_plan and not args.scenario:
        ap.error("--fault-plan requires --scenario (fault injection is bench/"
                 "test-mode only)")
    cfg = registry.get_smoke(args.arch) if args.smoke else registry.get_config(args.arch)

    reliability = None
    if (args.reliability or args.endurance_budget is not None
            or args.scrub_rate or args.drift_deadline_ms is not None):
        reliability = ReliabilityConfig(
            endurance_budget=args.endurance_budget,
            wear_leveling=not args.no_wear_leveling,
            scrub_rate=args.scrub_rate,
            drift_deadline_s=(args.drift_deadline_ms / 1e3
                              if args.drift_deadline_ms is not None else None))

    tracer = Tracer(capacity=args.trace_capacity) if args.trace_out else None
    obs_kw = {"tracer": tracer, "metrics_window": args.metrics_window,
              "reliability": reliability,
              "xla_annotations": args.xla_annotations,
              "deadline_s": (args.deadline_ms / 1e3
                             if args.deadline_ms is not None else None),
              "queue_timeout_s": (args.queue_timeout_ms / 1e3
                                  if args.queue_timeout_ms is not None else None),
              "degrade": args.degrade}

    if args.listen:
        block_size = args.block_size or 16
        max_len = args.max_len or (args.prompt_len + args.gen)
        max_len = -(-max_len // block_size) * block_size
        serve_listen(
            cfg, host=args.host, port=args.port,
            slots=args.slots or 4, max_len=max_len, block_size=block_size,
            max_queue=args.max_queue, tenant_rate=args.tenant_rate,
            tenant_burst=args.tenant_burst,
            heartbeat_s=(args.heartbeat_ms / 1e3
                         if args.heartbeat_ms is not None else None),
            n_blocks=args.kv_blocks, swap_blocks=args.swap_blocks,
            prefill_chunk=args.chunk, seed=args.seed,
            odin_mode=args.odin_mode, paged=not args.no_paged,
            prefix_sharing=False if args.no_prefix_sharing else None,
            mixed=False if args.no_mixed else None,
            mixed_budget=args.mixed_budget,
            horizon=args.horizon, spec_ngram=args.spec_ngram,
            eos_id=args.eos_id, temperature=args.temperature,
            top_k=args.top_k, sample_seed=args.sample_seed, **obs_kw)
        if tracer is not None:
            tracer.export(args.trace_out)
            print(f"[serve] wrote {len(tracer)} trace events to "
                  f"{args.trace_out} ({tracer.dropped_events} dropped)")
        return

    if args.scenario:
        if args.fault_plan:
            with open(args.fault_plan) as fh:
                obs_kw["fault_plan"] = FaultPlan.from_json(fh.read())
        spec = dataclasses.replace(SCENARIOS[args.scenario], n_requests=args.requests)
        block_size = args.block_size or 16
        max_len = max(spec.prompt_buckets) + spec.shared_prefix + max(spec.gen_buckets)
        max_len = -(-max_len // block_size) * block_size
        engine = ServingEngine(
            cfg, slots=args.slots or 4, max_len=max_len,
            block_size=block_size, n_blocks=args.kv_blocks,
            swap_blocks=args.swap_blocks, prefill_chunk=args.chunk,
            seed=args.seed, odin_mode=args.odin_mode,
            paged=not args.no_paged,
            prefix_sharing=False if args.no_prefix_sharing else None,
            mixed=False if args.no_mixed else None,
            mixed_budget=args.mixed_budget,
            horizon=args.horizon, spec_ngram=args.spec_ngram,
            eos_id=args.eos_id,
            temperature=args.temperature,
            top_k=args.top_k, sample_seed=args.sample_seed, **obs_kw)
        summary = engine.run(make_requests(cfg, spec, seed=args.seed))
        if tracer is not None:
            tracer.export(args.trace_out)
            print(f"[serve] wrote {len(tracer)} trace events to "
                  f"{args.trace_out} ({tracer.dropped_events} dropped)")
        print(json.dumps({k: v for k, v in summary.items() if k != "requests"},
                         indent=2, allow_nan=False))
        return

    if args.static and tracer is not None:
        ap.error("--trace-out requires the engine path (drop --static)")
    fn = serve_static if args.static else serve
    kw = {} if args.static else {"slots": args.slots,
                                 "block_size": args.block_size,
                                 "n_blocks": args.kv_blocks,
                                 "swap_blocks": args.swap_blocks,
                                 "prefill_chunk": args.chunk,
                                 "odin_mode": args.odin_mode,
                                 "paged": not args.no_paged,
                                 "prefix_sharing": False if args.no_prefix_sharing else None,
                                 "mixed": False if args.no_mixed else None,
                                 "mixed_budget": args.mixed_budget,
                                 "horizon": args.horizon,
                                 "spec_ngram": args.spec_ngram,
                                 "eos_id": args.eos_id,
                                 "temperature": args.temperature,
                                 "top_k": args.top_k,
                                 "sample_seed": args.sample_seed,
                                 **obs_kw}
    generated, tps = fn(cfg, batch=args.batch, prompt_len=args.prompt_len,
                        gen=args.gen, seed=args.seed, **kw)
    if tracer is not None:
        tracer.export(args.trace_out)
        print(f"[serve] wrote {len(tracer)} trace events to {args.trace_out} "
              f"({tracer.dropped_events} dropped)")
    print("[serve] first request tokens:", np.asarray(generated)[0].ravel()[:16])


if __name__ == "__main__":
    main()
