"""A hand-made device trace of a `qwen3_next` configuration's two programs,
for the `qwen3next` readers: like make_mla_trace.py's (events carry a
`tf_op` stat on their METADATA), written at test time with the durations
the test asks for. Times are microseconds from the trace's start; one chip.

"XLA Modules": jit__decode_multi_paged(1) [0, D)   jit__prefill_rows_paged(2) [P, P + 420 + chunk)
"XLA Ops", decode: gdn_proj 30, gdn_conv 10, gdn_step `step`, the paged
    kernel `kern`, attn_gate 5, moe_experts `experts`, lm_head 40
"XLA Ops", prefill: gdn_proj 100, gdn_conv 20, gdn_chunk `chunk`,
    moe_router 10, moe_dispatch 40, a ragged dot (the sorted expert
    matmuls, no scope of their own) 250: the delta rule's scopes are 120 +
    `chunk` (180: 300 of 600), the expert layer's the other 300.
host: bench.window over everything.
"""

from benchmark.tests.make_hybrid_trace import KERNEL
from benchmark.tests.make_scoped_trace import DEC, FIRST_REF, TF_OP
from benchmark.tests.make_synthetic_trace import field, plane

PRE = "jit(_prefill_rows_paged)/while/body/closed_call"


def layout(step=60.0, kern=50.0, experts=200.0, chunk=180.0, scoped=True):
    """(ops [(name, start, end, tf_op)], modules, host) in microseconds."""
    inner = DEC + "/closed_call/while/body/closed_call"
    decode = [("%fusion.1 = bf16[64,1,12288]{2,1,0} fusion(%a)", 30.0,
               inner + "/gdn_proj/bsd,de->bse/dot_general"),
              ("%fusion.2 = bf16[64,1,8192]{2,1,0} fusion(%x)", 10.0,
               inner + "/gdn_conv/mul"),
              ("%fusion.3 = f32[64,32,128,128]{3,2,1,0} fusion(%s)", step,
               inner + "/gdn_step/mul"),
              ("%paged_attention.4" + KERNEL, kern, DEC
               + "/closed_call/paged_attention/paged_attention/pallas_call"),
              ("%fusion.5 = bf16[64,1,4096]{2,1,0} fusion(%o, %g)", 5.0,
               DEC + "/closed_call/attn_gate/mul"),
              ("%fusion.6 = bf16[64,512]{1,0} fusion(%x, %w)", experts,
               inner + "/moe_experts/gd,df->gf/dot_general"),
              ("%fusion.7 = f32[64,1,37984]{2,1,0} fusion(%h)", 40.0,
               DEC + "/lm_head/bsd,dv->bsv/dot_general")]
    prefill = [("%fusion.8 = bf16[4,512,12288]{2,1,0} fusion(%a)", 100.0,
                PRE + "/while/body/gdn_proj/bsd,de->bse/dot_general"),
               ("%fusion.9 = bf16[4,512,8192]{2,1,0} fusion(%x)", 20.0,
                PRE + "/while/body/gdn_conv/mul"),
               ("%fusion.10 = f32[4,32,128,128]{3,2,1,0} fusion(%s)", chunk,
                PRE + "/while/body/gdn_chunk/while/body/bhck,bhkv->bhcv/"
                "dot_general"),
               ("%fusion.12 = f32[2048,512]{1,0} fusion(%h)", 10.0,
                PRE + "/while/body/moe_router/td,de->te/dot_general"),
               ("%sort.13 = s32[20480]{0} sort(%e)", 40.0,
                PRE + "/while/body/moe_dispatch/sort"),
               ("%ragged-dot-none.11 = bf16[5120,512]{1,0} custom-call(%x)",
                250.0, None)]
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
