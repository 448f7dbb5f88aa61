"""Writes data/scoped.xplane.pb: a hand-made XSpace like
make_synthetic_trace.py's, whose events also carry what a real TPU trace
keeps on the event METADATA: a `tf_op` stat with the op's scope path
(XEventMetadata.stats=5: XStat.metadata_id=1 and str_value=5, or
ref_value=7 naming a stat metadata whose name is the string;
XPlane.stat_metadata=5: map<int64, XStatMetadata{id=1,name=2}>). The
copy has none, as copies the compiler inserts have none on the chip.

Times are microseconds from the trace's start; one chip.

"XLA Modules": jit__decode_multi_paged(1) [0,200)   jit_step_fn(2) [300,500)
"XLA Ops", decode:  while.1 [0,200) spans the rest (not a leaf)
    paged_attention.10 (kernel) [0,80)      .../paged_attention/paged_attention/pallas_call
    reshape.294 [80,100)                    .../paged_attention/kv_gather/reshape
    dynamic-slice_bitcast_fusion.4 [100,120) .../while/body/squeeze  (no scope)
    copy.74 [120,150)                        no tf_op
    fusion.153 [150,160)                     .../kv_write/scatter
    fusion.7 [160,200)                       .../mlp/bsd,df->bsf/dot_general
  kv moves: 20 + 20 + 30 + 10 = 80 of 200
"XLA Ops", train step:
    fusion.20 [300,360)      jit(step_fn)/jvp()/while/body/closed_call/mlp/dot_general
    flash_fwd.3 (kernel) [360,400)  .../checkpoint/rematted_computation/attention/flash_fwd/pallas_call
    fusion.21 [400,440)      .../checkpoint/rematted_computation/mlp/dot_general
    fusion.22 [440,500)      .../checkpoint/mlp/transpose;.../checkpoint/mlp/mul
  recomputed: 40 + 40 = 80 of 400 busy
host: bench.window [0,500)  eng.host_drain [200,300) holding
      eng.device_wait [200,290) and eng.emit [290,300); engine.step [0,500)
"""

import os
import sys

from benchmark.tests.make_synthetic_trace import field, plane

DEC = "jit(_decode_multi_paged)/while/body/closed_call/while/body"
BWD = "jit(step_fn)/transpose(jvp())/while/body/closed_call/checkpoint"
KERNEL = ' = bf16[32,8,4,128]{3,2,1,0} custom-call(s32[4096]{0} %b), ' \
    'custom_call_target="tpu_custom_call"'

OPS = [   # name, start, end, tf_op
    ("%while.1 = (s32[]) while((s32[]) %t), body=%b", 0, 200,
     "jit(_decode_multi_paged)/while"),
    ("%paged_attention.10" + KERNEL, 0, 80,
     DEC + "/closed_call/paged_attention/paged_attention/pallas_call"),
    ("%reshape.294 = bf16[1878,32,1024]{2,1,0} reshape(bf16[1878,32,8,128] "
     "%p)", 80, 100, DEC + "/closed_call/paged_attention/kv_gather/reshape"),
    ("%dynamic-slice_bitcast_fusion.4 = bf16[1878,32,8,128]{3,2,1,0} "
     "fusion(bf16[12,1878,32,8,128] %p), kind=kLoop", 100, 120,
     DEC + "/squeeze"),
    ("%copy.74 = bf16[12,1878,32,8,128]{4,3,2,1,0} copy(bf16[12,1878,32,8,"
     "128] %p)", 120, 150, None),
    ("%fusion.153 = bf16[1878,32,8,128]{3,2,1,0} fusion(bf16[32,8,128] %k), "
     "kind=kLoop", 150, 160, DEC + "/closed_call/kv_write/scatter"),
    ("%fusion.7 = bf16[32,14336]{1,0} fusion(bf16[32,4096] %x), "
     "kind=kOutput", 160, 200,
     DEC + "/closed_call/mlp/bsd,df->bsf/dot_general"),
    ("%fusion.20 = bf16[4,4096,8192]{2,1,0} fusion(bf16[4,4096,2048] %x), "
     "kind=kOutput", 300, 360,
     "jit(step_fn)/jvp()/while/body/closed_call/mlp/dot_general"),
    ("%flash_fwd.3" + KERNEL, 360, 400,
     BWD + "/rematted_computation/attention/flash_fwd/pallas_call"),
    ("%fusion.21 = bf16[4,4096,8192]{2,1,0} fusion(bf16[4,4096,2048] %x), "
     "kind=kOutput", 400, 440,
     BWD + "/rematted_computation/mlp/dot_general"),
    ("%fusion.22 = bf16[4,4096,2048]{2,1,0} fusion(bf16[4,4096,8192] %g), "
     "kind=kOutput", 440, 500,
     BWD + "/mlp/transpose;" + BWD + "/mlp/mul"),
]
MODULES = [("jit__decode_multi_paged(1)", 0, 200), ("jit_step_fn(2)", 300, 500)]
HOST = [("bench.window", 0, 500), ("engine.step", 0, 500),
        ("eng.host_drain", 200, 300), ("eng.device_wait", 200, 290),
        ("eng.emit", 290, 300)]

TF_OP, FIRST_REF = 1, 10        # stat metadata ids


def device_plane() -> bytes:
    """Chip 0 with the stats on the event metadata. Every other tf_op is
    written as a str_value, the rest as a ref_value."""
    ids = {n: i + 1 for i, (n, _, _, _) in enumerate(OPS)}
    mods = {n: len(ids) + i + 1 for i, (n, _, _) in enumerate(MODULES)}
    body = field(1, 1) + field(2, "/device:TPU:0")
    for lid, (lname, evs, table) in enumerate(
            (("XLA Modules", MODULES, mods),
             ("XLA Ops", [o[:3] for o in OPS], ids)), 1):
        line = field(1, lid) + field(2, lname) + field(3, 0)
        for n, start, end in evs:
            line += field(4, field(1, table[n]) + field(2, start * 10**6)
                          + field(3, (end - start) * 10**6))
        body += field(3, line)
    refs = {}
    for k, (n, _, _, op) in enumerate(OPS):
        meta = field(1, ids[n]) + field(2, n)
        if op is not None and k % 2:
            meta += field(5, field(1, TF_OP) + field(5, op))
        elif op is not None:
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
    return field(1, body)


SPACE = device_plane() + plane(2, "/host:CPU", {"main/1": HOST}, 1000)
PATH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "scoped.xplane.pb")

if __name__ == "__main__":
    with open(PATH, "wb") as f:
        f.write(SPACE)
    sys.stdout.write(f"{len(SPACE)} bytes -> {PATH}\n")
