"""A hand-made device trace of a hybrid stack's decode program, for the
`phi4flash` readers: like make_moe_trace.py's (events carry a `tf_op` stat
on their METADATA), written at test time with the durations the test asks
for. Times are microseconds from the trace's start; one chip.

"XLA Modules": jit__decode_multi_paged(1) [0, D)   jit__prefill_rows_paged(2) [P, P + 90)
"XLA Ops", decode:
    fusion.1 40 us    .../ssm_proj/bsd,de->bse/dot_general
    fusion.2 scan us  .../ssm_scan/mul
    fusion.3 10 us    .../gmu/bse,ed->bsd/dot_general
    fusion.4 30 us    .../diff_combine/sub
    fusion.5 60 us    .../mlp/bsd,df->bsf/dot_general
    paged_attention.6 (kernel) kern us     paged_attention.7 (kernel) kern us
"XLA Ops", prefill (must not be counted): ssm_scan 50, the kernel 40.
host: bench.window over everything.
"""

from benchmark.tests.make_scoped_trace import DEC, FIRST_REF, KERNEL, TF_OP
from benchmark.tests.make_synthetic_trace import field, plane

PRE = "jit(_prefill_rows_paged)/while/body/closed_call"


def layout(scan: float = 25.0, kern: float = 50.0, scoped: bool = True):
    """(ops [(name, start, end, tf_op)], modules, host) in microseconds."""
    attn = "/closed_call/paged_attention/paged_attention/pallas_call"
    decode = [("%fusion.1 = bf16[64,1,10240]{2,1,0} fusion(%a)", 40.0,
               DEC + "/closed_call/ssm_proj/bsd,de->bse/dot_general"),
              ("%fusion.2 = f32[64,16,5120]{2,1,0} fusion(%s)", scan,
               DEC + "/closed_call/ssm_scan/mul"),
              ("%fusion.3 = bf16[64,1,2560]{2,1,0} fusion(%m)", 10.0,
               DEC + "/closed_call/gmu/bse,ed->bsd/dot_general"),
              ("%fusion.4 = f32[64,1,20,128]{3,2,1,0} fusion(%o)", 30.0,
               DEC + "/closed_call/diff_combine/sub"),
              ("%fusion.5 = bf16[64,1,10240]{2,1,0} fusion(%x)", 60.0,
               DEC + "/closed_call/mlp/bsd,df->bsf/dot_general"),
              ("%paged_attention.6" + KERNEL, kern, DEC + attn),
              ("%paged_attention.7" + KERNEL, kern, DEC + attn)]
    prefill = [("%fusion.8 = f32[4,16,5120]{2,1,0} fusion(%s)", 50.0,
                PRE + "/while/body/ssm_scan/mul"),
               ("%paged_attention.9" + KERNEL, 40.0, PRE + attn[12:])]
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


def space(scan: float = 25.0, kern: float = 50.0, scoped: bool = True
          ) -> bytes:
    ops, modules, host = layout(scan, kern, scoped)
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
