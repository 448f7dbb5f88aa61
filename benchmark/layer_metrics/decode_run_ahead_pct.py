"""Share of the window's fused decode dispatches that ran AHEAD: launched
chained off the block before them, before the host had pulled that
block, so the host's drain, replay and next dispatch hide under the
device's work instead of standing before every block. Difference of the
engine's counters `decode_dispatches_chained` and `decode_dispatches`
(`engine.stats()`, counted on the host at every launch) between the
snapshots at the window's two ends. 0 where the ring never engages (a
queue the engine takes for a pending admission, a row always
mid-prompt); what is left under 100 is the blocks that precede an
admission, a flush or a row's last token. Beside it, read by no metric:
`decode_dispatches_chained_queued`, those made while a request waited
behind full slots. None where the engine has no such counter."""

LAYER = "engine step loop, host"
UNIT = "%"
SOURCE = "program_counter"
MOVES = "out_tokens_per_s"


def read(records, reduced):
    a, b = records["snaps"].get("w0"), records["snaps"].get("w1")
    if not a or not b or "decode_dispatches_chained" not in b:
        return None
    launched = b["decode_dispatches"] - a.get("decode_dispatches", 0.0)
    chained = b["decode_dispatches_chained"] \
        - a.get("decode_dispatches_chained", 0.0)
    return 100.0 * chained / launched if launched > 0 else None
