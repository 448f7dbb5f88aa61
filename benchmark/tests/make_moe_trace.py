"""A hand-made device trace of a sparse model's two programs, for the
`moe_*` readers: like make_scoped_trace.py's (events carry a `tf_op` stat
on their METADATA), written at test time with the durations the test
asks for. Times are microseconds from the trace's start; one chip.

"XLA Modules": jit__decode_multi_paged(1) [0, D)   jit__prefill_rows_paged(2) [P, P + 400)
    with D = 150 + e1 + e2 and P = D rounded up to a whole us, + 100
"XLA Ops", decode (router, dispatch, two expert ops, a copy, the kernel):
    fusion.1 20 us   .../moe_router/gd,de->ge/dot_general
    fusion.2 10 us   .../moe_dispatch/reduce_sum
    fusion.3 e1 us   .../moe_experts/gd,edf->gef/dot_general
    fusion.4 e2 us   .../moe_experts/gef,efd->gd/dot_general
    copy.5   80 us   no tf_op
    paged_attention.6 (kernel) 40 us
"XLA Ops", prefill: attn_qkv 50, moe_dispatch (sort) 50, moe_experts 80 (the
    activation) + 120 (XLA's own `ragged-dot-none` kernel, whose op_name the
    compiler replaced: no scope), lm_head 100: the expert layer is 250 of 400.
host: bench.window over everything.
"""

from benchmark.tests.make_scoped_trace import DEC, FIRST_REF, KERNEL, TF_OP
from benchmark.tests.make_synthetic_trace import field, plane

PRE = "jit(_prefill_rows_paged)/while/body/closed_call"


def layout(e1: float = 100.0, e2: float = 50.0, scoped: bool = True):
    """(ops [(name, start, end, tf_op)], modules, host) in microseconds."""
    decode = [("%fusion.1 = f32[32,64]{1,0} fusion(bf16[32,2048] %x)", 20.0,
               DEC + "/closed_call/moe_router/gd,de->ge/dot_general"),
              ("%fusion.2 = f32[32,64]{1,0} fusion(f32[32,8,64] %o)", 10.0,
               DEC + "/closed_call/moe_dispatch/reduce_sum"),
              ("%fusion.3 = bf16[32,64,1024]{2,1,0} fusion(bf16[32,2048] %x)",
               e1, DEC + "/closed_call/moe_experts/gd,edf->gef/dot_general"),
              ("%fusion.4 = bf16[32,2048]{1,0} fusion(bf16[32,64,1024] %a)",
               e2, DEC + "/closed_call/moe_experts/gef,efd->gd/dot_general"),
              ("%copy.5 = bf16[10,615,32,16,128]{4,3,2,1,0} copy(%p)", 80.0,
               None),
              ("%paged_attention.6" + KERNEL, 40.0, DEC +
               "/closed_call/paged_attention/paged_attention/pallas_call")]
    prefill = [("%fusion.7 = bf16[4,512,16,128]{3,2,1,0} fusion(%x)", 50.0,
                PRE + "/attn_qkv/bsd,dhk->bshk/dot_general"),
               ("%sort.8 = (s32[16384]{0}) sort(s32[16384] %e)", 50.0,
                PRE + "/moe_dispatch/sort"),
               ("%fusion.9 = bf16[16384,1024]{1,0} fusion(%g, %u)", 80.0,
                PRE + "/moe_experts/mul"),
               ("%ragged-dot-none.1 = bf16[16384,1024]{1,0} custom-call(%m, "
                '%xs), custom_call_target="tpu_custom_call"', 120.0,
                "ragged-dot-none"),
               ("%fusion.10 = f32[4,512,50304]{2,1,0} fusion(%h)", 100.0,
                "jit(_prefill_rows_paged)/lm_head/bsd,dv->bsv/dot_general")]
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


def space(e1: float = 100.0, e2: float = 50.0, scoped: bool = True) -> bytes:
    ops, modules, host = layout(e1, e2, scoped)
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
