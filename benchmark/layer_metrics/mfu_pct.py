"""Model FLOP/s utilisation of the train step: this run's tokens per
second (in a traced run: of the part of the window before the profiler
started, which stalls the loop) times the benchmark's own FLOPs per token (6 x matmul parameters,
input embedding excluded, plus causal attention; recomputation not
counted) over chips x the published bf16 peak."""

from benchmark.harness import costs

LAYER = "train step"
UNIT = "%"
SOURCE = "host_clock"
MOVES = "train_tokens_per_s"


def read(records, reduced):
    peak = costs.peaks(records["device"]["kind"])["bf16_flops_per_s"]
    rate = records["tokens_per_s_untraced"]
    return 100.0 * rate * records["train_flops_per_token"] \
        / (records["chips"] * peak)
