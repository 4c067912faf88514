"""The row write-back kernel's share of its roofline.

Per unique id of each batch (counted on the host): read its code row
(d * bits / 8 bytes), step (4), Adam moments (2 * 4d), summed gradient (4d)
and id (4); write its code row, moments and the updated f32 row (4d).  The
rounding noise is not counted: a kernel can draw it on chip.  Bound by HBM
bandwidth.  Time: the summed device time of the ``sparse_row_update`` ops in
the window."""

KERNEL = r"sparse_row_update"


def bytes_per_unique(cfg: dict) -> float:
    d, bits = cfg["embedding"]["d"], cfg["embedding"]["bits"]
    code = d * bits / 8
    return (code + 4 + 8 * d + 4 * d + 4) + (code + 8 * d + 4 * d)


def read(run):
    red = run.reduced
    if red is None:
        return None
    seconds, _ = red.ops_matching(KERNEL)
    if seconds <= 0:
        return None
    need = run.counts["unique_ids"] * bytes_per_unique(run.config)
    return 100.0 * need / run.peaks["hbm_bytes_per_s"] / seconds
