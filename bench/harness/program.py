"""The system under test, and the only module of the benchmark that imports it.

From the program the benchmark takes its serving engine and front door,
the layout of its weight tree, its dispatch spans and counters, and its
compile-cache placement; nothing else.
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List

import jax
import numpy as np

from repro.launch.compile_cache import use_compile_cache  # noqa: F401
from repro.models import lm, registry
from repro.nn import module as nnmod
from repro.serving import FrontDoor, Request, ServingEngine, Tracer

__all__ = ["model_config", "weight_layout", "build_engine", "warm_up",
           "FrontDoor", "Request", "Tracer", "use_compile_cache"]

# published key → (where in the program's config, field)
_KEYS = {
    "hidden_size": ("model", "d_model"),
    "vocab_size": ("model", "vocab"),
    "rms_norm_eps": ("model", "norm_eps"),
    "tie_word_embeddings": ("model", "tie_embeddings"),
    "num_hidden_layers": ("block", "n_layers"),
    "intermediate_size": ("block", "d_ff"),
    "num_attention_heads": ("attn", "n_heads"),
    "num_key_value_heads": ("attn", "n_kv_heads"),
    "head_dim": ("attn", "d_head"),
    "rope_theta": ("attn", "rope_theta"),
}


def model_config(config: Dict):
    """The program's ``ModelConfig`` for a configuration file: the registry
    architecture named in ``program.arch`` with every published key of
    ``hf_config`` applied.  Keys the program cannot express are refused."""
    hf = dict(config["hf_config"])
    hf.setdefault("head_dim", hf["hidden_size"] // hf["num_attention_heads"])
    if hf.get("partial_rotary_factor", 1.0) != 1.0:
        raise ValueError("the program rotates whole heads only")
    if hf.get("rope_scaling"):
        raise ValueError("the program has no rotary scaling")
    if hf.get("hidden_act", "silu") != "silu":
        raise ValueError("the program's dense MLP here is SwiGLU (silu)")
    cfg = registry.get_config(config["program"]["arch"])
    if len(cfg.blocks) != 1 or cfg.blocks[0].kind != "dense":
        raise ValueError("one dense segment expected")
    model, block, attn = {}, {}, {}
    for key, (where, field) in _KEYS.items():
        {"model": model, "block": block, "attn": attn}[where][field] = hf[key]
    b = cfg.blocks[0]
    b = dataclasses.replace(b, attn=dataclasses.replace(b.attn, **attn),
                            **block)
    return dataclasses.replace(cfg, blocks=(b,), **model)


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
    then a tile of ``q``).  Returns the tile widths warmed."""
    chunk = int(engine["prefill_chunk"])
    tiles = [1 << k for k in range(chunk.bit_length()) if 1 << k <= chunk]
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFF, 3])
    vocab = eng.cfg.vocab
    for k, q in enumerate(tiles):
        n = chunk + q if q < chunk else chunk
        eng.run([Request(rid=-1 - k, max_new=2,
                         prompt=rng.integers(0, vocab, n).astype(np.int32))])
    jax.block_until_ready(eng.caches)
    return tiles
