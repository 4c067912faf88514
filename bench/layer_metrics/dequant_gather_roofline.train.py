"""The lookup kernel's share of its roofline in a training cell.

Bytes follow the work, not today's kernel: one lookup of the step's batch,
and per looked-up id a code row (d * bits / 8 bytes), a 4-byte step, a
4-byte id and a d * 4-byte f32 output row.  Bound by HBM bandwidth (the
lookup does no arithmetic to speak of).  Time: the summed device time of
the ``dequant_gather`` ops in the window, mean over chips."""

KERNEL = r"dequant_gather"


def bytes_per_lookup(cfg: dict) -> float:
    d, bits = cfg["embedding"]["d"], cfg["embedding"]["bits"]
    return d * bits / 8 + 4 + 4 + 4 * d


def read(run):
    red = run.reduced
    if red is None:
        return None
    seconds, _ = red.ops_matching(KERNEL)
    steps = len(red.span_busy("bench.train_step"))
    if seconds <= 0 or steps == 0:
        return None
    per_chip = run.counts["lookups_per_step"] / run.counts["chips"]
    need = steps * per_chip * bytes_per_lookup(run.config)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
