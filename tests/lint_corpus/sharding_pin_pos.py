"""sharding-pin positives: donated carries decay to default placement.

Never imported — linted as AST by tests/test_lint_corpus.py.
"""

import jax.numpy as jnp


class Engine:
    def swap_in(self, row, logits):
        # POSITIVE: host-side scatter into a donated carry with no re-pin
        # before the next dispatch — the tp layout decays to replicated.
        self._last_logits = self._last_logits.at[row].set(
            jnp.asarray(logits))

    def rebuild_pool(self, shape):
        # POSITIVE: fresh host-built pool [L, NB, T, KV*D], never pinned.
        self._pool_k = jnp.zeros(shape, jnp.bfloat16)
        self._pool_v = jnp.zeros(shape, jnp.bfloat16)

    def rebuild_scales(self, layers, blocks, kv_heads):
        # POSITIVE: a quantized pool's scale slab rides the same donated
        # carry as the pool; rebuilt on the host, it decays the same way.
        self._scale_k = jnp.zeros((layers, blocks, kv_heads), jnp.float32)
