"""A hand-made device trace of a `deepseek_v32` configuration's two
programs, for the `dsv32` readers: like make_moe_trace.py's (events carry a
`tf_op` stat on their METADATA), written at test time with the durations
the test asks for. Times are microseconds from the trace's start; one chip.

"XLA Modules": jit__decode_multi_paged(1) [0, D)   jit__prefill_rows_paged(2) [P, P + 500)
"XLA Ops", decode: mla_proj 30, indexer_score `score`, indexer_topk 20,
    latent_gather `gather`, sparse_attention `attend`, moe_experts
    `experts`, moe_shared 10, lm_head 40 (D = 100 + the four)
"XLA Ops", prefill: mla_proj 100, indexer_score 120, indexer_topk 60,
    latent_gather 30, sparse_attention 40, moe_experts 150: selection and
    its attention are 250 of 500.
host: bench.window over everything.
"""

from benchmark.tests.make_scoped_trace import DEC, FIRST_REF, TF_OP
from benchmark.tests.make_synthetic_trace import field, plane

PRE = "jit(_prefill_rows_paged)/while/body/closed_call"


def layout(score=80.0, gather=25.0, attend=50.0, experts=200.0,
           scoped=True):
    """(ops [(name, start, end, tf_op)], modules, host) in microseconds."""
    decode = [("%fusion.1 = bf16[24,1,128,640]{3,2,1,0} fusion(%x)", 30.0,
               DEC + "/closed_call/mla_proj/bshn,chn->bshc/dot_general"),
              ("%fusion.2 = f32[24,1,18432]{2,1,0} fusion(%q, %k)", score,
               DEC + "/closed_call/indexer_score/bqjd,bsd->bqjs/dot_general"),
              ("%sort.3 = (f32[24,1,18432]{2,1,0}) sort(%s)", 20.0,
               DEC + "/closed_call/indexer_topk/top_k"),
              ("%gather.4 = bf16[24,1,2048,640]{3,2,1,0} gather(%p, %i)",
               gather, DEC + "/closed_call/latent_gather/gather"),
              ("%fusion.5 = bf16[24,1,128,512]{3,2,1,0} fusion(%p, %r)",
               attend,
               DEC + "/closed_call/sparse_attention/bqhk,bqkc->bqhc/"
               "dot_general"),
              ("%fusion.6 = bf16[24,16,2048]{2,1,0} fusion(%x, %w)", experts,
               DEC + "/closed_call/moe_experts/gd,edf->gef/dot_general"),
              ("%fusion.7 = bf16[24,7168]{1,0} fusion(%x, %w)", 10.0,
               DEC + "/closed_call/moe_shared/gf,fd->gd/dot_general"),
              ("%fusion.8 = f32[24,1,16160]{2,1,0} fusion(%h)", 40.0,
               DEC + "/lm_head/bsd,dv->bsv/dot_general")]
    prefill = [("%fusion.9 = bf16[4,512,128,640]{3,2,1,0} fusion(%x)", 100.0,
                PRE + "/mla_proj/bshn,chn->bshc/dot_general"),
               ("%fusion.10 = f32[4,16,18432]{2,1,0} fusion(%q, %k)", 120.0,
                PRE + "/while/body/indexer_score/bqjd,bsd->bqjs/"
                "dot_general"),
               ("%sort.11 = (f32[4,16,18432]{2,1,0}) sort(%s)", 60.0,
                PRE + "/while/body/indexer_topk/top_k"),
               ("%gather.12 = bf16[4,16,2048,640]{3,2,1,0} gather(%p, %i)",
                30.0, PRE + "/while/body/latent_gather/gather"),
               ("%fusion.13 = bf16[4,16,128,512]{3,2,1,0} fusion(%p, %r)",
                40.0, PRE + "/while/body/sparse_attention/bqhk,bqkc->bqhc/"
                "dot_general"),
               ("%fusion.14 = bf16[2048,2048]{1,0} fusion(%g, %u)", 150.0,
                PRE + "/while/body/moe_experts/mul")]
    ops, t = [], 0.0
    for name, dur, op in decode:
        ops.append((name, t, t + dur, op if scoped else None))
        t += dur
    end_decode = t
    start_prefill = t = float(int(end_decode) + 101)
    for name, dur, op in prefill:
        ops.append((name, t, t + dur, op if scoped else None))
        t += dur
    modules = [("jit__decode_multi_paged(1)", 0.0, end_decode),
               ("jit__prefill_rows_paged(2)", start_prefill, t)]
    return ops, modules, [("bench.window", 0, int(t) + 100)]


def space(**kw) -> bytes:
    ops, modules, host = layout(**kw)
    ids = {n: i + 1 for i, (n, _, _, _) in enumerate(ops)}
    mods = {n: len(ids) + i + 1 for i, (n, _, _) in enumerate(modules)}
    body = field(1, 1) + field(2, "/device:TPU:0")
    for lid, (lname, evs, table) in enumerate(
            (("XLA Modules", modules, mods),
             ("XLA Ops", [o[:3] for o in ops], ids)), 1):
        line = field(1, lid) + field(2, lname) + field(3, 0)
        for n, start, end in evs:
            line += field(4, field(1, table[n])
                          + field(2, int(round(start * 10**6)))
                          + field(3, int(round((end - start) * 10**6))))
        body += field(3, line)
    refs = {}
    for n, _, _, op in ops:
        meta = field(1, ids[n]) + field(2, n)
        if op is not None:
            ref = refs.setdefault(op, FIRST_REF + len(refs))
            meta += field(5, field(1, TF_OP) + field(7, ref))
        body += field(4, field(1, ids[n]) + field(2, meta))
    for n, i in mods.items():
        body += field(4, field(1, i) + field(2, field(1, i) + field(2, n)))
    body += field(5, field(1, TF_OP) + field(
        2, field(1, TF_OP) + field(2, "tf_op")))
    for op, ref in refs.items():
        body += field(5, field(1, ref) + field(2, field(1, ref)
                                               + field(2, op)))
    return field(1, body) + plane(2, "/host:CPU", {"main/1": host}, 1000)
