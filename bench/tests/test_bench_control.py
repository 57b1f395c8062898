"""The control at a size a test run holds: the plain reference computed
with int8 operands reads a wider mean gap than the bf16 program does on
the same prompts and served tokens, and fails the limit the program
keeps."""
import dataclasses

import numpy as np

from bench import weights
from bench.reference import dense
from bench.tests.conftest import TINY, TINY_LIMIT, TINY_SERVE

M = dict(TINY, hidden_act="silu", mlp_gated=True, attention_bias=True,
         norm="rmsnorm", rms_norm_eps=1e-5, rope_theta=1e6,
         partial_rotary_factor=1.0, tie_word_embeddings=True)


def test_int8_control_fails_where_the_program_passes():

    from repro.configs import ServeConfig, get_arch, reduced
    from repro.serving import Engine
    cfg = dataclasses.replace(reduced(get_arch("qwen2-0.5b")), remat="none")
    params = weights.make(M, seed=11)
    eng = Engine(cfg, ServeConfig(**TINY_SERVE), params=params, seed=11)
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, M["vocab_size"], n).tolist()
               for n in (40, 55, 70, 90, 33, 61)]
    results, _ = eng.run_offline(prompts, 60)
    prog, ctrl = [], []
    for p, r in zip(prompts, results):
        assert len(r.tokens) == 60
        prog.append(dense.served_gaps(M, params, p, r.tokens))
        ctrl.append(dense.served_gaps(M, params, p, r.tokens, control=True))
    prog, ctrl = np.concatenate(prog).mean(), np.concatenate(ctrl).mean()
    assert prog <= TINY_LIMIT < ctrl, (prog, ctrl)
