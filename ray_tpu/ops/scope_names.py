"""The names the device programs give their parts.

Every jitted program of `ray_tpu.models` wraps its parts in
`jax.named_scope(<one of SCOPES>)` and every Pallas kernel of
`ray_tpu.ops` passes `name=<one of KERNELS>` to `pallas_call`. A scope
is metadata of the compiled program (the op's `op_name`, which a
profiler trace shows as the device event's `tf_op`): it changes no
instruction, no buffer and no result. The names live here, once, so that
whatever reads a trace (`benchmark/harness/scopes.py`) matches on the
list the program was written against and not on instruction numbers,
which change with every compile.
"""

EMBED = "embed"                      # token ids -> hidden states
NORM = "norm"                        # every rmsnorm and LayerNorm
ATTN_QKV = "attn_qkv"                # q/k/v projections and rope
KV_WRITE = "kv_write"                # this chunk's K/V into cache or pool
KV_GATHER = "kv_gather"              # slices/reshapes/gathers of cached
#                                      K/V ahead of attention
PAGED_ATTENTION = "paged_attention"  # attention through a block table
CACHED_ATTENTION = "cached_attention"    # attention over a dense cache row
ATTENTION = "attention"              # uncached causal attention (training)
ATTN_OUT = "attn_out"                # output projection and residual
MLP = "mlp"                          # gated feed-forward and residual
MOE_ROUTER = "moe_router"            # router matmul, softmax, top-k
MOE_DISPATCH = "moe_dispatch"        # sort, gather, scatter, combine: moves
#                                      tokens to experts, computes nothing
MOE_EXPERTS = "moe_experts"          # the three expert matmuls and silu
MOE_SHARED = "moe_shared"            # the shared expert beside the routed ones
MLA_PROJ = "mla_proj"                # latent attention: low-rank q and kv
#                                      projections, their norms and rotary,
#                                      the absorbed key and value maps
INDEXER_SCORE = "indexer_score"      # sparse attention's indexer: every live
#                                      token of a row scored for a query
INDEXER_TOPK = "indexer_topk"        # ... and the exact top-k of the scores
LATENT_GATHER = "latent_gather"      # a row's latent pages side by side
SPARSE_ATTENTION = "sparse_attention"    # attention over the chosen rows
SSM_PROJ = "ssm_proj"                # state-space layer: in/out projections,
#                                      causal conv, gate
SSM_SCAN = "ssm_scan"                # state-space layer: x_proj, dt and the
#                                      recurrence over the state
GMU = "gmu"                          # gated memory unit (reads the memory a
#                                      state-space layer handed on)
DIFF_COMBINE = "diff_combine"        # differential attention: subtraction,
#                                      sub-norm and scale of a head pair
GDN_PROJ = "gdn_proj"                # gated delta-rule layer: in/out
#                                      projections, gates, head norm
GDN_CONV = "gdn_conv"                # ... its causal depthwise conv and the
#                                      conv state's reads and writes
GDN_CHUNK = "gdn_chunk"              # ... the delta rule's chunkwise form (a
#                                      prefill chunk) with the state it moves
GDN_STEP = "gdn_step"                # ... its one-token update (a decode
#                                      token) with the state it moves
ATTN_GATE = "attn_gate"              # gated attention: sigmoid(gate) on the
#                                      heads' output
SWA_PROJ = "swa_proj"                # a latent WINDOW layer (an `MlaConfig`
#                                      with `layer_types`): its low-rank
#                                      projections, norms, rotary, absorbed map
SWA_WRITE = "swa_write"              # ... its latent rows into the window plane
SWA_ATTENTION = "swa_attention"      # ... the pages that cover its queries'
#                                      windows, the mask, and attention over them
SWA_GATE = "swa_gate"                # ... its head-wise output gate (a full
#                                      layer's is `attn_gate`)
LM_HEAD = "lm_head"                  # final vocab projection
SAMPLE = "sample"                    # on-device sampling and row freezing
LOSS = "loss"                        # log-softmax and token nll
OPTIMIZER = "optimizer"              # update rule and parameter apply

SCOPES = (EMBED, NORM, ATTN_QKV, KV_WRITE, KV_GATHER, PAGED_ATTENTION,
          CACHED_ATTENTION, ATTENTION, ATTN_OUT, MLP, LM_HEAD, SAMPLE,
          LOSS, OPTIMIZER, MOE_ROUTER, MOE_DISPATCH, MOE_EXPERTS,
          SSM_PROJ, SSM_SCAN, GMU, DIFF_COMBINE, MOE_SHARED, MLA_PROJ,
          INDEXER_SCORE, INDEXER_TOPK, LATENT_GATHER, SPARSE_ATTENTION,
          GDN_PROJ, GDN_CONV, GDN_CHUNK, GDN_STEP, ATTN_GATE, SWA_PROJ,
          SWA_WRITE, SWA_ATTENTION, SWA_GATE)

# Scopes whose ops move cached K/V without computing on it.
KV_MOVE = (KV_WRITE, KV_GATHER, SWA_WRITE)

# `pallas_call(name=...)`: the name is in the kernel's custom call, so a
# trace tells the kernels apart.
FLASH_FWD = "flash_fwd"
FLASH_BWD_DQ = "flash_bwd_dq"
FLASH_BWD_DKV = "flash_bwd_dkv"
PAGED_KERNEL = "paged_attention"
SPARSE_LATENT_KERNEL = "sparse_latent_attention"
SPARSE_LATENT_DECODE_KERNEL = "sparse_latent_decode"
HIT_EXPERTS_KERNEL = "moe_hit_experts"
HELD_GROUPED_KERNEL = "moe_held_grouped"
DELTA_STEP_KERNEL = "gdn_delta_step"     # under the scope `gdn_step`
INDEXER_SELECT_KERNEL = "indexer_select"    # under `indexer_score` and
#                                             `indexer_topk` (see `mla`)

KERNELS = (PAGED_KERNEL, FLASH_FWD, FLASH_BWD_DQ, FLASH_BWD_DKV,
           SPARSE_LATENT_KERNEL, SPARSE_LATENT_DECODE_KERNEL,
           HIT_EXPERTS_KERNEL, HELD_GROUPED_KERNEL, DELTA_STEP_KERNEL,
           INDEXER_SELECT_KERNEL)

