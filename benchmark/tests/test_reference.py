"""The plain reference against the program's own forward pass at nano
size on the CPU, and the rehearsal twin of every cell end to end."""

import os
import subprocess
import sys

import numpy as np
import pytest

from benchmark.harness import spec

MODEL = {"hidden_size": 64, "intermediate_size": 128,
         "num_hidden_layers": 2, "num_attention_heads": 4,
         "num_key_value_heads": 2, "vocab_size": 256,
         "rms_norm_eps": 1e-5, "rope_theta": 1e6, "torch_dtype": "float32"}


def test_reference_matches_forward_cached_and_llama_loss():
    import jax
    import jax.numpy as jnp

    from benchmark.harness.model import llama_config
    from benchmark.reference import llama_dense
    from ray_tpu.models import llama_init, llama_loss
    from ray_tpu.models.generate import forward_cached, init_cache

    cfg = llama_config(MODEL, 48, activation_dtype="float32",
                       param_dtype="float32", remat=False)
    params = llama_init(jax.random.PRNGKey(3), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(4), (2, 40), 0, 256)
    want, _ = forward_cached(params, tokens, init_cache(cfg, 2, 48), 0, cfg)
    got = llama_dense.logits(params, tokens, MODEL)
    # both float32; the orders of summation differ
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=0)
    # in two pieces through the cache, as an engine would: still the same
    cache = init_cache(cfg, 2, 48)
    a, cache = forward_cached(params, tokens[:, :25], cache, 0, cfg)
    b, _ = forward_cached(params, tokens[:, 25:], cache, 25, cfg)
    np.testing.assert_allclose(np.asarray(got), np.concatenate(
        [np.asarray(a), np.asarray(b)], axis=1), atol=2e-5, rtol=0)
    batch = jnp.concatenate([tokens, tokens[:, :1]], axis=1)
    assert float(llama_dense.loss(params, batch, MODEL, chunk=8)) == \
        pytest.approx(float(llama_loss(params, {"tokens": batch}, cfg)),
                      abs=2e-6)
    # margins: a sequence that follows the reference's own greedy choice
    # has margin 0 everywhere; a wrong token has a positive one
    seq = [int(t) for t in tokens[0, :8]]
    for _ in range(6):
        lg = llama_dense.logits(params, jnp.asarray([seq]), MODEL)[0, -1]
        seq.append(int(jnp.argmax(lg)))
    m = np.asarray(llama_dense.margins(params, jnp.asarray(seq), 8, MODEL))
    assert m.shape == (6,) and np.all(m == 0)
    seq[-1] = (seq[-1] + 1) % 256
    m = np.asarray(llama_dense.margins(params, jnp.asarray(seq), 8, MODEL))
    assert m[-1] > 0 and np.all(m[:-1] == 0)


def test_reference_refuses_a_sliding_window():
    import jax.numpy as jnp

    from benchmark.reference import llama_dense

    with pytest.raises(ValueError):
        llama_dense.hidden({}, jnp.zeros((1, 4), jnp.int32),
                           dict(MODEL, sliding_window=4096))


@pytest.mark.parametrize("cell,trace", [("mistral7b-chat", "1"),
                                        ("mistral7b-rollout", "0"),
                                        ("internlm2-train-fsdp4", "1")])
def test_rehearsal_twin_runs_end_to_end(cell, trace):
    r = subprocess.run(
        [sys.executable, "benchmark/run.py", "--workload", cell,
         "--seed", str(2**31 + 5), "--trace", trace, "--rehearse"],
        cwd=spec.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert r.returncode == 0, r.stderr[-2000:]
    last = r.stdout.strip().splitlines()[-1]
    assert '"rehearsal": "ok"' in last and '"correct": true' in last
    assert '"metrics"' not in r.stdout          # a rehearsal prints none
    assert '"compiles_in_window": 0' in r.stdout
