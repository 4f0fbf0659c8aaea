"""chip_smoke.py at smoke width on the CPU: the serve and reference phases
the chip run relies on (kernel interpreted here), and the device check that
refuses to run anywhere but a TPU."""
import json
import os
import subprocess
import sys

import jax
import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.models import lm, registry  # noqa: E402
from repro.nn import module as nnmod  # noqa: E402


def test_serve_and_reference_phases_at_smoke_width():
    cfg = registry.get_smoke(chip_smoke.ARCH)
    params = nnmod.materialize(lm.param_spec(cfg), jax.random.PRNGKey(0))
    gen = 6
    prompt_lens = (8, 20, 30, 44)
    engine, reqs, toks, _ = chip_smoke.serve_phase(
        cfg, params, prompt_lens=prompt_lens, gen=gen, slots=4, max_len=56,
        block_size=8, chunk=16, seed=0)
    assert all(r.state.value == "done" for r in reqs)
    assert [len(t) for t in toks] == [gen] * len(reqs)
    assert engine.stats.mixed_dispatches > 0
    assert engine.stats.mixed_decode_rows > 0
    i = int(np.argmax(prompt_lens))
    # smoke logits are ~10x smaller than full width: a margin to match
    out = chip_smoke.reference_phase(cfg, params, reqs[i].prompt, toks[i],
                                     margin=0.01)
    assert out["tokens"] == gen
    assert out["checked"] >= 1 and out["mismatches_checked"] == 0
    # the margin rule bites: a wrong token above the margin fails the phase
    bad = list(toks[i])
    bad[0] = (bad[0] + 1) % cfg.vocab
    with pytest.raises(chip_smoke.SmokeFailure):
        chip_smoke.reference_phase(cfg, params, reqs[i].prompt, bad,
                                   margin=-1.0)


def test_device_check_refuses_cpu(tmp_path):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path))
    proc = subprocess.run([sys.executable, os.path.join(ROOT, "chip_smoke.py")],
                          env=env, cwd=tmp_path, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last["ok"] is False and last["device"]["platform"] == "cpu"
