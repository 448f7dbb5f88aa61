"""The gated delta rule (Gated DeltaNet's recurrence), in the two forms a
serving engine needs.

A head keeps a matrix state ``S`` ``[dk, dv]`` float32. A token with key
``k`` and query ``q`` (both L2-normalised, ``q`` scaled), value ``v``,
log-decay ``g <= 0`` and write strength ``beta`` in (0, 1) does

    S <- exp(g) S;   d = beta (v - S^T k);   S <- S + k d^T;   o = S^T q

`delta_step` is that, for one token a row (a decode step): two reductions
over the state as it was (``S^T k`` and ``S^T q``; ``o`` follows from them
without a second look at the new state: ``o = exp(g) S^T q + (k . q) d``)
and one pass that writes the new state.

`delta_chunks` is the chunkwise form for a prefill chunk (the published
algorithm: Yang et al., "Gated Delta Networks", and the WY representation
of "Parallelizing Linear Transformers with the Delta Rule"). With ``G_i``
the running sum of ``g`` inside a chunk of `CHUNK` tokens and

    A[i, j] = beta_i (k_i . k_j) exp(G_i - G_j),  j < i   (else 0)
    T = (I + A)^-1
    U = T (beta v),   W = T (beta k exp(G))

a chunk that starts from state ``S`` has, for all its tokens at once,

    V' = U - W S                                  (the d of every token)
    O  = (q exp(G)) S + tril(q k^T exp(G_i - G_j)) V'
    S <- exp(G_last) S + (k exp(G_last - G_i))^T V'

so the state moves through HBM once a chunk and not once a token, and all
that is sequential is a scan over chunks. ``T`` is the inverse of a unit
lower-triangular matrix: it is built by halves (`_unit_lower_inverse`:
``[[P, 0], [R, Q]]^-1 = [[P^-1, 0], [-Q^-1 R P^-1, Q^-1]]``, six levels for
64), which is forward substitution in blocks and as stable, where the
series ``I - A + A^2 - ...`` cancels catastrophically once keys repeat.

A position that is not ``live`` (bucket filler behind a chunk's real
tokens, a frozen row) has ``g = 0`` and ``beta = 0``: it writes nothing
and decays nothing, so the state after the chunk is the state after its
live prefix.

Everything the state touches is float32; the matmuls take their operands
in ``dtype`` (the model's, bf16 on the chip) and accumulate in float32,
the triangular inverse runs at the highest precision.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from ray_tpu.ops import scope_names as sn

CHUNK = 64
_HI = jax.lax.Precision.HIGHEST


def l2norm(x, eps: float = 1e-6):
    """x / sqrt(sum(x^2) + eps) over the last axis, float32."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + eps)


def delta_step(state, q, k, v, g, beta, live):
    """One token a row. ``state`` [B, H, dk, dv] float32; ``q``, ``k``
    [B, H, dk] and ``v`` [B, H, dv] float32 (q, k normalised, q scaled);
    ``g``, ``beta`` [B, H] float32; ``live`` [B] bool: only live rows
    advance. Returns (o [B, H, dv] float32, the new state)."""
    with jax.named_scope(sn.GDN_STEP):
        decay = jnp.exp(g)[..., None]                          # [B, H, 1]
        # both reductions read the state as it was (multiply and add, not
        # a matmul of one row: exact float32, and one fused read)
        ks = jnp.sum(k[..., :, None] * state, axis=-2)
        qs = jnp.sum(q[..., :, None] * state, axis=-2)
        d = beta[..., None] * (v - decay * ks)
        o = decay * qs + jnp.sum(k * q, axis=-1, keepdims=True) * d
        new = decay[..., None] * state + k[..., :, None] * d[..., None, :]
        return o, jnp.where(live[:, None, None, None], new, state)


def _unit_lower_inverse(a):
    """(I + a)^-1 for ``a`` [..., n, n] STRICTLY lower triangular, n a
    power of two, by halves from 1 x 1 blocks up, on whole n x n matrices
    (blocks of 1, 2 or 4 as arrays of their own would leave a tile nearly
    empty). ``x`` holds the inverses of the diagonal blocks of size b and
    zeros elsewhere; ``low`` is ``a`` inside the lower-left block of each
    PAIR of them, and ``x - x low x`` fills exactly those blocks with
    ``-Q^-1 R P^-1``."""
    n = a.shape[-1]
    i = jnp.arange(n)
    x = jnp.broadcast_to(jnp.eye(n, dtype=a.dtype), a.shape)
    b = 1
    while b < n:
        pair = (i[:, None] // (2 * b)) == (i[None, :] // (2 * b))
        low = jnp.where(pair & ((i[:, None] // b) % 2 == 1)
                        & ((i[None, :] // b) % 2 == 0), a, 0.0)
        x = x - jnp.einsum("...ij,...jk,...kl->...il", x, low, x,
                           precision=_HI)
        b *= 2
    return x


def delta_chunks(state, q, k, v, g, beta, live, dtype):
    """A chunk of ``S`` tokens a row, `CHUNK` at a time. ``state``
    [B, H, dk, dv] float32; ``q``, ``k`` [B, S, H, dk], ``v`` [B, S, H, dv]
    float32; ``g``, ``beta`` [B, S, H] float32; ``live`` [B, S] bool, a
    prefix of each row. Returns (o [B, S, H, dv] float32, the state after
    each row's live tokens)."""
    B, S, H, dk = q.shape
    dv = v.shape[-1]
    f32 = jnp.float32
    pad = -S % CHUNK
    with jax.named_scope(sn.GDN_CHUNK):
        lv = live[..., None]
        g = jnp.where(lv, g, 0.0)
        beta = jnp.where(lv, beta, 0.0)
        if pad:
            q, k, v, g, beta = (
                jnp.pad(x, ((0, 0), (0, pad)) + ((0, 0),) * (x.ndim - 2))
                for x in (q, k, v, g, beta))
        n = (S + pad) // CHUNK

        def chunked(x):                 # [B, n*C, H, ...] -> [n, B, H, C, ...]
            x = x.reshape(B, n, CHUNK, *x.shape[2:])
            return jnp.moveaxis(jnp.moveaxis(x, 3, 2), 1, 0)

        q, k, v, g, beta = (chunked(x) for x in (q, k, v, g, beta))
        G = jnp.cumsum(g, axis=-1)                             # [n,B,H,C]
        tri = jnp.tril(jnp.ones((CHUNK, CHUNK), bool))
        # exp(G_i - G_j) where j <= i, 0 above the diagonal
        decay = jnp.exp(jnp.where(tri, G[..., :, None] - G[..., None, :],
                                  -jnp.inf))
        kb = k * beta[..., None]
        kk = jnp.einsum("...ik,...jk->...ij", kb, k, precision=_HI)
        a = jnp.where(jnp.tril(tri, -1), kk * decay, 0.0)
        t = _unit_lower_inverse(a)
        u = jnp.einsum("...ij,...jv->...iv", t, v * beta[..., None],
                       precision=_HI)
        w = jnp.einsum("...ij,...jk->...ik", t, kb * jnp.exp(G)[..., None],
                       precision=_HI)
        qk = jnp.einsum("...ik,...jk->...ij", q.astype(dtype),
                        k.astype(dtype), preferred_element_type=f32) * decay
        q_in = (q * jnp.exp(G)[..., None]).astype(dtype)
        g_last = G[..., -1]                                    # [n,B,H]
        k_out = (k * jnp.exp(g_last[..., None] - G)[..., None]).astype(dtype)

        def step(s, xs):
            u_c, w_c, qk_c, q_c, k_c, gl = xs
            sd = s.astype(dtype)
            vn = u_c - jnp.einsum("bhck,bhkv->bhcv", w_c.astype(dtype), sd,
                                  preferred_element_type=f32)
            o = jnp.einsum("bhck,bhkv->bhcv", q_c, sd,
                           preferred_element_type=f32) \
                + jnp.einsum("bhij,bhjv->bhiv", qk_c.astype(dtype),
                             vn.astype(dtype), preferred_element_type=f32)
            s = jnp.exp(gl)[..., None, None] * s \
                + jnp.einsum("bhck,bhcv->bhkv", k_c, vn.astype(dtype),
                             preferred_element_type=f32)
            return s, o

        state, o = jax.lax.scan(step, state,
                                (u, w, qk, q_in, k_out, g_last))
        # [n, B, H, C, dv] -> [B, n*C, H, dv]
        o = jnp.moveaxis(jnp.moveaxis(o, 0, 1), 3, 2).reshape(
            B, n * CHUNK, H, dv)
        return o[:, :S], state
