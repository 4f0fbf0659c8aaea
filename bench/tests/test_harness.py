"""The harness's phases at smoke width on the CPU (Pallas interpreted).

A run with the timed path sound must come out correct; a run with it
broken underneath — a step that returns its state unchanged, or a token
altered where it is produced — must come out not correct.  The other
faults a check can plant do not exist in these cells: no cell takes a mean
over a batch (no training), and none spans chips.  On a CPU the command
line refuses to run and prints no result.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

import run as runmod
import smoke
from harness.spec import BENCH_DIR

# sound smoke runs read logit gaps of 0 to 1.4e-3 (seeds 1, 2, 3, 2**35+1);
# a wrong token at smoke width lies tenths below the best
LIMITS = {"max_logit_gap": {"limit": 0.02}, "mean_logit_gap": {"limit": 0.004}}
SEED = 2 ** 33 + 7


def _run(cell=None, seed=SEED):
    return runmod.run(cell or smoke.cell(), seed, 1.5, False,
                      require_chip=False, cache=False, limits=LIMITS,
                      t_start=time.perf_counter())


@pytest.mark.parametrize("loop", ["closed", "open"])
def test_sound_run_is_correct(loop):
    res = _run(smoke.cell(loop))
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res["checks"]) == ["max_logit_gap", "mean_logit_gap",
                                   "failed_requests", "short_streams"]
    assert list(res)[-1] == "checks"
    m = res["metrics"]
    assert set(m) == {"ttft_p50_ms", "itl_p99_ms", "output_tok_s",
                      "prompt_tok_s", "setup_s"}
    assert all(v["value"] > 0 for v in m.values())
    assert res["device"]["platform"] == "cpu"


def _stateless(make):
    def build(*a, **k):
        step = make(*a, **k)

        def broken(params, caches, *rest, **kw):
            out = step(params, caches, *rest, **kw)
            return out[:-1] + (caches,)       # the state comes back unchanged
        return broken
    return build


def test_step_returning_state_unchanged_is_caught(monkeypatch):
    from repro.serving import engine as eng
    monkeypatch.setattr(eng, "make_serving_decode_step",
                        _stateless(eng.make_serving_decode_step))
    monkeypatch.setattr(eng, "make_serving_mixed_step",
                        _stateless(eng.make_serving_mixed_step))
    res = _run()
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > LIMITS[
        "max_logit_gap"]["limit"]


def test_altered_token_is_caught(monkeypatch):
    from repro.serving.engine import ServingEngine
    emit = ServingEngine._emit

    def altered(self, req, tok, now):
        if len(req.generated) == 2:           # every request's third token
            tok = (np.asarray(tok) + 1) % self.cfg.vocab
        return emit(self, req, tok, now)

    monkeypatch.setattr(ServingEngine, "_emit", altered)
    res = _run()
    assert not res["correct"]
    assert res["checks"]["max_logit_gap"]["value"] > LIMITS[
        "max_logit_gap"]["limit"]


# a wider smoke model for the control: at d_model 64 the logits are too
# small for any precision to matter
WIDE = dict(smoke.HF, hidden_size=256, intermediate_size=512,
            num_hidden_layers=4, head_dim=64, vocab_size=4096)


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_control_comes_out_not_correct(seed):
    """The reference in fp8, put in the program's place, reads the gap of the
    tokens it would serve; the benchmark's own comparison judges it not
    correct on every seed, where the program is correct (CPU readings of
    the widest gap: program 0 to 0.0086, int8 0.015 to 0.037, fp8 0.14 to
    0.19)."""
    res = runmod.run(smoke.cell(hf=WIDE), seed, 1.5, False,
                     require_chip=False, cache=False, limits=LIMITS,
                     controls=("fp8",), t_start=time.perf_counter())
    assert res["correct"], res["checks"]
    ctrl = res["control_checks"]["fp8"]
    assert not ctrl["correct"], ctrl
    assert ctrl["checks"]["max_logit_gap"]["value"] > 3 * res["checks"][
        "max_logit_gap"]["value"]


def _command(cwd, env):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi4mini-docs-closed",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"], cwd=cwd,
        capture_output=True, text=True, env=env, timeout=300)


def test_command_refuses_without_a_chip():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = _command(os.path.dirname(BENCH_DIR), env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "refused: needs 1 TPU chip(s)" in p.stderr


def test_command_fails_with_only_the_benchmark(tmp_path):
    """A checkout holding only BENCHMARK.json and bench/ has no program."""
    root = os.path.dirname(BENCH_DIR)
    shutil.copy(os.path.join(root, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    p = _command(tmp_path, env)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "No module named 'repro'" in p.stderr


FAMILY = ("model_config", "forward_rows", "token_flops", "prompt_flops",
          "paged_attn_flops", "paged_attn_bytes")


def test_benchmark_names_resolve():
    """Every cell finds its configuration, model family, traffic and metric
    readers, and every metric entry is some cell's."""
    from harness.spec import load_cell, metric_reader
    with open(os.path.join(os.path.dirname(BENCH_DIR), "BENCHMARK.json")) as f:
        bench = json.load(f)
    used = set()
    for w in bench["workloads"]:
        cell = load_cell(w["name"])
        assert {"setup_s"} < {m["name"] for m in cell.end_to_end}
        assert all(callable(getattr(cell.family, f)) for f in FAMILY)
        assert cell.per_layer
        for m in cell.per_layer:
            assert callable(metric_reader(m["name"]))
            assert m["moves"] in {e["name"] for e in cell.end_to_end}
        used |= {m["name"] for m in cell.end_to_end + cell.per_layer}
    assert used == {m["name"] for m in bench["end_to_end"] + bench["per_layer"]}
    assert "phi3medium-chat-closed" in {w["name"] for w in bench["workloads"]}


def test_sample_spans_the_limits_files_floor_in_requests():
    """The longest request alone can reach the token target; the sample
    still takes others until it holds ``sample_requests`` of them."""
    from types import SimpleNamespace as NS
    recs = [NS(i=i, finished=True, prompt_len=8, max_new=n,
               tokens=[0] * n) for i, n in enumerate([600] + [20] * 20)]
    assert len(runmod.draw_sample(recs, SEED)) == 1
    sample = runmod.draw_sample(recs, SEED, min_requests=8)
    assert len(sample) == 8 and sample[0] is recs[0]
    assert len({r.i for r in sample}) == 8
    assert len(runmod.draw_sample(recs, SEED, 99)) == runmod.SAMPLE_MAX
    with open(os.path.join(BENCH_DIR, "limits",
                           "phi3medium-chat-closed.json")) as f:
        assert json.load(f)["sample_requests"] > 1


def test_warm_up_takes_the_copy_on_write_fork():
    """A prompt whose first token equals a cached prompt's forks a block;
    the warm-up takes that path, so its programs compile in set-up."""
    from harness import program, weights
    cell = smoke.cell()
    cfg = cell.family.model_config(cell.config)
    params = weights.make_params(program.weight_layout(cfg), SEED)
    eng = program.build_engine(cfg, params, cell.config["engine"])
    program.warm_up(eng, cell.config["engine"], SEED)
    assert eng.prefix_sharing and eng.stats.cow_forks == 1
