"""A hand-made device trace of a `dots3_note` configuration's two
programs, for the `swa_*` readers: `make_mla_trace`'s decode and prefill
executions with the WINDOW layers' ops added, written at test time with
the durations the test asks for. Times are microseconds from the trace's
start; one chip.

"XLA Ops", decode: make_mla_trace's eight (mla_proj .. lm_head), then
    swa_proj 20, swa_write 5, swa_attention `window` (a gather of the
    window's pages 10 + the kernel `window` - 10), swa_gate 2
"XLA Ops", prefill: make_mla_trace's six (500 us), then swa_proj 60,
    swa_write 10, swa_attention `chunk`, swa_gate 5: with `chunk` 125 the
    window layers' four scopes are 200 of 700.
"""

from benchmark.tests import make_mla_trace
from benchmark.tests.make_mla_trace import PRE
from benchmark.tests.make_scoped_trace import DEC


def layout(window=60.0, chunk=125.0, scoped=True, **kw):
    """(ops, modules, host) as `make_mla_trace.layout`, the window layers'
    ops appended to each program's execution."""
    ops, modules, _ = make_mla_trace.layout(scoped=scoped, **kw)
    n_dec = 8
    decode, prefill = ops[:n_dec], ops[n_dec:]
    more_dec = [
        ("%fusion.20 = bf16[64,1,64,1152]{3,2,1,0} fusion(%x)", 20.0,
         DEC + "/closed_call/swa_proj/bshn,chn->bshc/dot_general"),
        ("%scatter.21 = bf16[3,409,256,1152]{3,2,1,0} scatter(%p, %l)", 5.0,
         DEC + "/closed_call/swa_write/scatter"),
        ("%gather.22 = s32[64,3]{1,0} gather(%bt, %i)", 10.0,
         DEC + "/closed_call/swa_attention/take_along_axis/gather"),
        ("%custom-call.23 = bf16[64,64,1024]{2,1,0} custom-call(%q, %p)",
         window - 10.0,
         DEC + "/closed_call/swa_attention/sparse_latent_decode"),
        ("%fusion.24 = bf16[64,1,64]{2,1,0} fusion(%a, %w)", 2.0,
         DEC + "/closed_call/attn_out/swa_gate/bsd,dh->bsh/dot_general")]
    more_pre = [
        ("%fusion.30 = bf16[4,512,64,1152]{3,2,1,0} fusion(%x)", 60.0,
         PRE + "/swa_proj/bshn,chn->bshc/dot_general"),
        ("%scatter.31 = bf16[3,409,256,1152]{3,2,1,0} scatter(%p, %l)", 10.0,
         PRE + "/swa_write/scatter"),
        ("%custom-call.32 = bf16[4,64,512,1024]{3,2,1,0} custom-call(%q)",
         chunk, PRE + "/swa_attention/sparse_latent_attention"),
        ("%fusion.33 = bf16[4,512,64]{2,1,0} fusion(%a, %w)", 5.0,
         PRE + "/attn_out/swa_gate/bsd,dh->bsh/dot_general")]
    out, t = [], 0.0
    for name, start, end, op in decode:
        out.append((name, t, t + end - start, op))
        t += end - start
    for name, dur, op in more_dec:
        out.append((name, t, t + dur, op if scoped else None))
        t += dur
    end_decode = t
    start_prefill = t = float(int(end_decode) + 101)
    for name, start, end, op in prefill:
        out.append((name, t, t + end - start, op))
        t += end - start
    for name, dur, op in more_pre:
        out.append((name, t, t + dur, op if scoped else None))
        t += dur
    modules = [(modules[0][0], 0.0, end_decode),
               (modules[1][0], start_prefill, t)]
    return out, modules, [("bench.window", 0, int(t) + 100)]


def space(**kw) -> bytes:
    """The trace as an xplane: `make_mla_trace.space`'s writer over this
    file's layout."""
    made, plain = layout(**kw), make_mla_trace.layout
    make_mla_trace.layout = lambda: made
    try:
        return make_mla_trace.space()
    finally:
        make_mla_trace.layout = plain
