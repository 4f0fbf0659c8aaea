"""The sparse-expert family (``bench/families/moe_gqa.py``): its key map and
refusals, its reference against the program at smoke width, its counts
against hand counts, and the expert layer's reader."""
import dataclasses
import json
import os
from types import SimpleNamespace as NS

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from harness import program, weights
from harness.spec import BENCH_DIR, SpecError, load_family, metric_reader

SEED = 2 ** 33 + 7
# a smoke-width Qwen3-MoE: 2 of 8 experts held, top-2
HF = {"hidden_size": 64, "num_hidden_layers": 2, "num_attention_heads": 4,
      "num_key_value_heads": 2, "head_dim": 16, "num_experts": 2,
      "num_experts_per_tok": 2, "moe_intermediate_size": 32,
      "norm_topk_prob": True, "decoder_sparse_step": 1, "mlp_only_layers": [],
      "vocab_size": 256, "tie_word_embeddings": False, "rms_norm_eps": 1e-6,
      "rope_theta": 1e6, "rope_scaling": None, "sliding_window": None,
      "hidden_act": "silu"}


def _config(**hf):
    return {"hf_config": dict(HF, **hf), "published": {"num_experts": 8},
            "program": {"arch": "qwen3-moe-235b-a22b", "family": "moe_gqa"}}


def _file():
    with open(os.path.join(BENCH_DIR, "configs",
                           "qwen3-moe-235b-a22b.json")) as f:
        return json.load(f)


def test_published_keys_reach_the_program():
    cfg = load_family("moe_gqa").model_config(_file())
    b = cfg.blocks[0]
    assert (cfg.d_model, cfg.vocab, cfg.norm_eps, cfg.tie_embeddings) == (
        4096, 151936, 1e-6, False)
    assert (b.n_layers, b.attn.n_heads, b.attn.n_kv_heads, b.attn.d_head,
            b.attn.rope_theta, b.attn.qk_norm) == (12, 64, 4, 128, 1e6, True)
    m = b.moe
    assert (m.n_experts, m.held, m.held_start, m.top_k, m.d_ff,
            m.n_shared) == (128, 8, 0, 8, 1536, 0)


@pytest.mark.parametrize("key,value", [
    ("norm_topk_prob", False), ("shared_expert_intermediate_size", 256),
    ("mlp_only_layers", [0]), ("decoder_sparse_step", 2),
    ("sliding_window", 4096), ("rope_scaling", {"type": "yarn"}),
    ("attention_bias", True), ("hidden_act", "gelu")])
def test_keys_the_program_cannot_express_are_refused(key, value):
    with pytest.raises(ValueError):
        load_family("moe_gqa").model_config(_config(**{key: value}))


def test_a_program_without_the_norm_or_the_share_is_refused(monkeypatch):
    """A program whose attention has no q/k norm, or whose expert layer is
    not told which experts it holds, never runs the model: ``SpecError``,
    which the command line turns into exit 2 and no result."""
    @dataclasses.dataclass(frozen=True)
    class Attn:
        n_heads: int = 8

    @dataclasses.dataclass(frozen=True)
    class MoE:
        n_experts: int = 8

    old = NS(blocks=(NS(kind="moe", attn=Attn(), moe=MoE()),))
    monkeypatch.setattr(program, "base_config", lambda arch: old)
    with pytest.raises(SpecError, match="lacks"):
        load_family("moe_gqa").model_config(_config())


def test_reference_is_the_program_at_smoke_width():
    """The family's float32 reference and the program's forward pass, both
    in float32 on the benchmark's weights, give the same logits: the
    reference routes over all 8 experts, normalises over both picks and
    adds the 2 held experts' part, as the program does.  (Float32 rounding
    leaves ~1e-6 of logits up to ~4; 1e-4 is far under what a missing q/k
    norm or share moves.)"""
    fam = load_family("moe_gqa")
    cfg = fam.model_config(_config())
    params = weights.make_params(program.weight_layout(cfg), SEED)
    params = jax.tree.map(lambda a: a.astype(jnp.float32), params)
    tokens = np.random.default_rng(3).integers(0, 256, (2, 40)).astype(np.int32)
    from repro.models import lm
    logits = np.asarray(lm.forward(params, jnp.asarray(tokens), cfg)[0])
    rows = fam.forward_rows(params, HF, jnp.asarray(
        np.pad(tokens, ((0, 0), (0, 216)))), jnp.arange(2 * 256), None)
    ref = np.asarray(rows @ params["lm_head"]).reshape(2, 256, -1)[:, :40]
    assert np.abs(ref - logits).max() < 1e-4


def test_flops_match_hand_count():
    """The cell's file: d 4096, 64/4 heads of 128, 12 layers, router 128,
    8 of 128 experts held, top-8, expert width 1536, vocab 151936.
      per layer: attention projections 2·(4096·8192 + 2·4096·512 +
        8192·4096) = 142,606,336; router 2·4096·128 = 1,048,576; experts
        8·8/128 · 3·2·4096·1536 = 18,874,368: 162,529,280; × 12
      attention at 1000 keys: 12 · 4 · 64 · 128 · 1000
      head: 2 · 4096 · 151936
      paged kernel bytes at 1000 keys: 12 · (2·1000·4·128·2 + 2·64·128·2)"""
    hf = _file()["hf_config"]
    f = load_family("moe_gqa")
    mm, attn, head = 12 * 162_529_280, 393_216_000, 1_244_659_712
    assert f.layer_matmul_flops(hf) == mm
    assert f.attention_flops(hf, 1000) == attn
    assert f.head_flops(hf) == head
    assert f.token_flops(hf, 999, True) == mm + attn + head
    assert f.paged_attn_flops(hf, 1000) == attn
    assert f.paged_attn_bytes(hf, 1000) == 24_969_216
    assert f.prompt_flops(hf, 3) == 3 * mm + attn / 1000 * 6 + head


def test_expert_row_util_reads_the_counters():
    read = metric_reader("expert_row_util.moe")
    assert read(NS(stats={"moe_routed": 4608, "moe_held": 288,
                          "moe_rows": 4608})) == 6.25
    assert read(NS(stats={"steps": 3})) is None          # a dense program
    assert read(NS(stats={"moe_held": 0, "moe_rows": 0})) is None
