"""The system under test, and the only module of the benchmark that imports it.

From the program the benchmark takes its serving engine and front door,
the layout of its weight tree, its dispatch spans and counters, its
compile-cache placement, and the registry architecture on which a model
family lays the published keys; nothing else.
"""
from __future__ import annotations

from typing import Dict, List

import jax
import numpy as np

from repro.launch.compile_cache import use_compile_cache  # noqa: F401
from repro.models import lm, registry
from repro.nn import module as nnmod
from repro.serving import FrontDoor, Request, ServingEngine, Tracer

__all__ = ["base_config", "weight_layout", "build_engine", "warm_up",
           "FrontDoor", "Request", "Tracer", "use_compile_cache"]


def base_config(arch: str):
    """The registry's ``ModelConfig`` for ``arch``, on which a model family
    (``bench/families/<family>.py``) lays the published keys."""
    return registry.get_config(arch)


def weight_layout(cfg):
    """ShapeDtypeStructs of the program's weight tree."""
    return nnmod.abstract(lm.param_spec(cfg))


def build_engine(cfg, params, engine: Dict, *, tracer=None, clock=None,
                 annotate: bool = False) -> ServingEngine:
    return ServingEngine(cfg, params=params, tracer=tracer, clock=clock,
                         xla_annotations=annotate, **engine)


def warm_up(eng: ServingEngine, engine: Dict, seed: int) -> List[int]:
    """Compile every program the traffic can reach, one request at a time:
    the decode step and the mixed step at each power-of-two tile width up to
    the prefill chunk (a prompt of ``chunk + q`` rows runs a full chunk,
    then a tile of ``q``); then, where the engine shares prefixes, the
    copy-on-write fork of a partly matched block, which any prompt whose
    first token equals a cached prompt's takes.  Returns the tile widths
    warmed."""
    chunk = int(engine["prefill_chunk"])
    tiles = [1 << k for k in range(chunk.bit_length()) if 1 << k <= chunk]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 3])
    vocab = eng.cfg.vocab
    prompts = []
    for k, q in enumerate(tiles):
        n = chunk + q if q < chunk else chunk
        prompts.append(rng.integers(0, vocab, n).astype(np.int32))
        eng.run([Request(rid=-1 - k, max_new=2, prompt=prompts[-1])])
    if eng.prefix_sharing:
        head = prompts[-1][:1]
        tail = rng.integers(0, vocab, int(engine["block_size"]))
        eng.run([Request(rid=-1 - len(tiles), max_new=2, prompt=np.concatenate(
            [head, tail.astype(np.int32)]))])
    jax.block_until_ready(eng.caches)
    return tiles
