"""The training step's share of the chip's bf16 peak: the DCN's forward and
backward matmul FLOPs per sample (backward = 2x forward) times the samples
trained in the traced window, over the device's busy time in that window
times chips times the peak.  ALPT's second forward/backward for the
step-size gradient is not counted, so this bounds from below what the step
does."""


def dcn_forward_flops(cfg: dict) -> int:
    """Matmul FLOPs of one DCN forward for one sample, from the shapes."""
    d0 = cfg["data"]["fields"] * cfg["embedding"]["d"]
    model = cfg["model"]
    flops = 2 * d0 * model["cross_depth"]
    prev = d0
    for w in model["mlp_widths"]:
        flops += 2 * prev * w
        prev = w
    return flops + 2 * (d0 + prev)


def read(run):
    red = run.reduced
    if red is None:
        return None
    flops = 3 * dcn_forward_flops(run.config) * run.counts["samples"]
    # busy_s is the mean over chips, so busy_s x chips is the device time.
    return 100.0 * flops / (red.busy_s * run.counts["chips"] * run.peaks["bf16_flops"])
