"""Whole step: pairs scored in the window times sm-cnn's operations per
pair, over window x chips x bf16 peak. The program's float32 matmuls run
as one bf16 pass on the chip, so the bf16 peak is the denominator."""
from bench import flops
from bench import spans as S
from bench.peaks import peaks


def read(run):
    rows = S.hist_sum(run.registry, "batcher_batch_rows")
    if not rows:
        return None
    peak = peaks(run.device_kind)["bf16_flops"] * run.cell.chips
    return 100.0 * rows * flops.pair_flops(run.model) / (run.window_s * peak)
