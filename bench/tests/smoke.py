"""A cell at smoke width for the CPU tests: the harness's phases run end to
end with the Pallas kernels interpreted.  Nothing here is a device number."""
from harness.spec import Cell, load_family

HF = {"hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
      "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
      "vocab_size": 256, "rms_norm_eps": 1e-5, "rope_theta": 10000.0,
      "hidden_act": "silu", "tie_word_embeddings": True}
ENGINE = {"slots": 4, "max_len": 128, "block_size": 16, "prefill_chunk": 32,
          "n_blocks": 32}
LENGTHS = {"prompt": {"dist": "lognormal", "median": 20, "sigma": 0.6,
                      "min": 8, "max": 80},
           "output": {"dist": "uniform", "min": 4, "max": 12}}


def cell(loop: str = "closed", hf=None) -> Cell:
    traffic = dict(LENGTHS, ramp_s=0.5, loop=loop, pool=64,
                   order_seed=12)
    if loop == "open":
        traffic["rate_rps"] = 4.0
    else:
        traffic["clients"] = 6
    e2e = [{"name": n, "unit": u} for n, u in
           (("ttft_p50_ms", "ms"), ("itl_p99_ms", "ms"),
            ("output_tok_s", "tokens/s"), ("prompt_tok_s", "tokens/s"),
            ("setup_s", "s"))]
    per_layer = [{"name": n, "unit": u} for n, u in
                 (("mixed_step_ms.poisson", "ms"), ("decode_step_ms.chat", "ms"),
                  ("mixed_tile_util.docs", "%"))]
    return Cell(name="smoke", chips=1, config_name="smoke",
                config={"hf_config": dict(hf or HF),
                        "program": {"arch": "phi4-mini-3.8b",
                                    "family": "dense_gqa"},
                        "engine": dict(ENGINE)},
                family=load_family("dense_gqa"),
                traffic_name="smoke", traffic=traffic, end_to_end=e2e,
                per_layer=per_layer)
