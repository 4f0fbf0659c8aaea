"""Model families: the configuration names its family, the harness finds it
by that name, and the dense family reads as the reference did before it was
a family of its own."""
import hashlib
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
from harness import program, reference, spec, trace_reduce, weights
from harness.spec import BENCH_DIR, load_family

ROOT = os.path.dirname(BENCH_DIR)
FIXTURES = os.path.join(BENCH_DIR, "tests", "fixtures", "families")
SEED = 2 ** 33 + 7

# The reference's gaps before the dense family was split out of the harness
# (``harness/reference.py`` and ``harness/program.py`` as they stood), at
# SEED on this XLA CPU build: per reading, the count, the float64 sum and
# the largest gap, and sha256 of the float32 gaps (first 16 hex digits).
GOLDEN = {
    "tied": {
        "gap": (56, 27.462965175509453, 0.8814810514450073,
                "9e3c8b590edfc0af"),
        "control_gap/int8": (56, 0.02469480037689209, 0.008415013551712036,
                             "4da8dab12003e789"),
        "control_gap/fp8": (56, 0.3514633774757385, 0.0895855724811554,
                            "0de922c1f405be6a")},
    "untied": {
        "gap": (56, 186.75956046581268, 5.917392730712891,
                "a306914b8cef9faa"),
        "control_gap/int8": (56, 0.1898190975189209, 0.11190509796142578,
                             "94b4fb64071fbb94"),
        "control_gap/fp8": (56, 1.4541244506835938, 0.40498805046081543,
                            "8702485d89a412bf")},
}
CASES = {"tied": dict(smoke.HF),
         "untied": dict(smoke.HF, hidden_size=128, intermediate_size=256,
                        num_hidden_layers=3, head_dim=32, vocab_size=1000,
                        tie_word_embeddings=False)}


@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_family_reads_as_before(case):
    """Program gaps and both controls, bit for bit, through the family."""
    hf = CASES[case]
    fam = load_family("dense_gqa")
    cfg = fam.model_config({"hf_config": hf,
                            "program": {"arch": "phi4-mini-3.8b"}})
    params = weights.make_params(program.weight_layout(cfg), SEED)
    rng = np.random.default_rng(11)
    seqs = [(rng.integers(0, hf["vocab_size"], n).astype(np.int32),
             rng.integers(0, hf["vocab_size"], m).astype(np.int32))
            for n, m in ((9, 5), (40, 12), (23, 1), (70, 30), (5, 8))]
    got = reference.logit_gaps(fam.forward_rows, params, hf, seqs,
                               ("int8", "fp8"), batch=2)
    assert set(got) == set(GOLDEN[case])
    for name, (n, total, top, digest) in GOLDEN[case].items():
        v = got[name]
        assert (len(v), float(np.sum(v, dtype=np.float64)),
                float(np.max(v))) == (n, total, top), name
        assert hashlib.sha256(np.asarray(v, np.float32).tobytes()
                              ).hexdigest()[:16] == digest, name


@pytest.mark.parametrize("family", [None, "no_such_family", "../harness/spec"])
def test_config_without_a_family_is_refused(tmp_path, family):
    """No ``program.family``, or one that names no file under
    ``bench/families``: exit 2, no result, before any device is touched."""
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(BENCH_DIR, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    path = tmp_path / "bench" / "configs" / "phi4-mini-3.8b.json"
    config = json.loads(path.read_text())
    if family is None:
        del config["program"]["family"]
    else:
        config["program"]["family"] = family
    path.write_text(json.dumps(config))
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "phi4mini-docs-closed",
         "--seed", str(SEED), "--seconds", "1", "--trace", "0"],
        cwd=tmp_path, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 2
    assert p.stdout.strip() == ""
    said = ("phi4-mini-3.8b.json names no program.family" if family is None
            else f"no model family {family!r}")
    assert said in p.stderr and "refused:" in p.stderr


def test_a_new_family_is_found_by_its_file(monkeypatch):
    """A family module dropped into the family directory is found by the name
    the configuration gives, and every call into it comes through it: the key
    map, the weights' ``draw``, the reference and the readers' counts."""
    cell = smoke.cell()
    monkeypatch.setattr(spec, "FAMILY_DIR", FIXTURES)
    fam = load_family("toy_gqa")
    cell.family = fam
    cell.config["program"]["family"] = "toy_gqa"
    cell.per_layer = [{"name": "step_mfu.chat", "unit": "%"}]
    # the CPU's trace has no device plane: stand in for its reduction
    monkeypatch.setattr(trace_reduce, "reduce_file", lambda path: (
        trace_reduce.Reduced(window_s=1.0, busy_s=0.5, op_seconds={},
                             gaps=[])))
    res = runmod.run(cell, SEED, 1.5, True, require_chip=False, cache=False,
                     limits={"max_logit_gap": {"limit": 0.02}},
                     t_start=time.perf_counter())
    assert res["correct"], res["checks"]
    calls = fam.CALLS
    assert calls["model_config"] == 1 and calls["forward_rows"] > 0
    assert calls["draw"] > 0
    assert calls.get("prompt_flops", 0) + calls.get("token_flops", 0) > 0


def test_harness_names_no_family_key():
    """The driver, the program's wrapper, the reference's driver and the
    readers' shared code name no key or weight of one family."""
    dense = load_family("dense_gqa")
    generic = {"vocab_size", "tie_word_embeddings"}
    words = ({k for k in dense._KEYS if k not in generic}
             | {"w_gate", "w_up", "w_down", "ln1", "ln2", '"q"', '"k"'})
    for rel in ("run.py", "harness/program.py", "harness/reference.py",
                "harness/layer.py"):
        with open(os.path.join(BENCH_DIR, rel)) as f:
            text = f.read()
        assert not [w for w in words if w in text], rel
