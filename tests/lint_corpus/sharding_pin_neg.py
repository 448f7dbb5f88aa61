"""sharding-pin negatives: every carry rebuild is pinned.

Never imported — linted as AST by tests/test_lint_corpus.py.
"""

import functools

import jax
import jax.numpy as jnp


@functools.partial(jax.jit, donate_argnames=("pool_k", "pool_v"))
def _cow_blocks(pool_k, pool_v, src, dst, shardings=None):
    return pool_k, pool_v


class Engine:
    def swap_in(self, row, logits):
        # NEGATIVE: the repo convention — host scatter, immediate re-pin.
        self._last_logits = self._last_logits.at[row].set(
            jnp.asarray(logits))
        if self._shardings is not None:
            self._last_logits = jax.device_put(self._last_logits,
                                               self._shardings.logits)

    def cow(self, src, dst):
        # NEGATIVE: produced inside jit — pinning is the jit's contract.
        self._pool_k, self._pool_v = _cow_blocks(
            self._pool_k, self._pool_v, src, dst,
            shardings=self._shardings)

    def init_scales(self, layers, blocks, kv_heads):
        # NEGATIVE: the scale slab [L, NB, KV] built on the host and
        # pinned before the first dispatch, like the pool beside it.
        self._scale_k = jnp.zeros((layers, blocks, kv_heads), jnp.float32)
        if self._shardings is not None:
            self._scale_k = jax.device_put(self._scale_k,
                                           self._shardings.scale)

    def init_draft_pool(self, cfg):
        # NEGATIVE: explicit sharding kwarg at the build site.
        self._pool_dk = build_pool(cfg, sharding=self._shardings.d_pool)

    def teardown(self):
        # NEGATIVE: None sentinel and plain moves never decay a layout.
        self._pool_k = self._pool_v = None
        self._last_logits = self._checkpoint_logits
