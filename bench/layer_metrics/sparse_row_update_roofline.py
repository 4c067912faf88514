"""The row write-back's share of its roofline.

Per unique id of each batch (counted on the host): read its code row
(d * bits / 8 bytes), step (4), Adam moments (2 * 4d), summed gradient (4d)
and id (4); write its code row, moments and the updated f32 row (4d).  The
rounding noise is not counted: a kernel can draw it on chip.  Bound by HBM
bandwidth.  These are the least bytes the work needs, whatever does it.
Time: the device time under the program's ``lpt.row_update`` scope in the
window (its row gathers, the row Adam + SR requantize and its scatters),
mean over chips."""

SCOPE = "lpt.row_update"


def bytes_per_unique(cfg: dict) -> float:
    d, bits = cfg["embedding"]["d"], cfg["embedding"]["bits"]
    code = d * bits / 8
    return (code + 4 + 8 * d + 4 * d + 4) + (code + 8 * d + 4 * d)


def read(run):
    red = run.reduced
    if red is None:
        return None
    seconds = red.scope_seconds(SCOPE)
    if seconds <= 0:
        return None
    need = run.counts["unique_ids"] * bytes_per_unique(run.config)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
