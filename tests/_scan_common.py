"""What tests/test_scan_decode.py and tests/test_scan_parity.py share: the
served model, a mixed continuous-batching workload, and one engine run of
it. Two files so that ``--dist loadfile`` can give them to two workers."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nanosandbox_tpu.config import GPTConfig
from nanosandbox_tpu.models.gpt import GPT
from nanosandbox_tpu.serve import Engine


@pytest.fixture(scope="module")
def served_model():
    cfg = GPTConfig(n_layer=2, n_head=2, n_embd=32, block_size=64,
                    vocab_size=50, dropout=0.0, compute_dtype="float32",
                    attention_impl="xla")
    model = GPT(cfg)
    params = model.init(jax.random.key(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    return cfg, model, params


def _mixed_reqs(n=10, seed=0, vocab=50, eos=None):
    rng = np.random.default_rng(seed)
    return [(rng.integers(0, vocab, int(rng.integers(2, 40))).tolist(),
             int(rng.integers(2, 12)), int(rng.integers(0, 99)), eos)
            for _ in range(n)]


def _run(model, params, reqs, **kw):
    eng = Engine(model, params, num_slots=4, max_len=64, **kw)
    for prompt, mnt, seed, eos in reqs:
        eng.submit(prompt, mnt, seed=seed, eos_id=eos)
    out = {r.rid: (r.tokens, r.finish_reason) for r in eng.drain()}
    assert len(out) == len(reqs)
    return eng, out
