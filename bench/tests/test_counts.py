"""Work counts of the per-layer metrics against hand arithmetic."""
import harness

LM = harness.BENCH / "layer_metrics"


def _cfg(bits, fields=2, d=16, depth=1, widths=(3,)):
    return {"data": {"fields": fields}, "embedding": {"d": d, "bits": bits},
            "model": {"cross_depth": depth, "mlp_widths": list(widths)}}


def test_lookup_bytes_int8_and_int4():
    m = harness.load_module(LM / "dequant_gather_roofline.train.py")
    # 4 ids at d=16: code row 16 B (int8) / 8 B (int4), step 4, id 4, f32 row 64.
    assert 4 * m.bytes_per_lookup(_cfg(8)) == 4 * (16 + 4 + 4 + 64) == 352
    assert 4 * m.bytes_per_lookup(_cfg(4)) == 4 * (8 + 4 + 4 + 64) == 320


def test_row_update_bytes():
    m = harness.load_module(LM / "sparse_row_update_roofline.py")
    # read: code 16, step 4, mu 64, nu 64, grad 64, id 4; write: code 16, mu, nu, row 64 each.
    assert m.bytes_per_unique(_cfg(8)) == (16 + 4 + 64 + 64 + 64 + 4) + (16 + 64 + 64 + 64)
    assert m.bytes_per_unique(_cfg(4)) == (8 + 4 + 192 + 4) + (8 + 192)


def test_dcn_forward_flops():
    m = harness.load_module(LM / "train_mfu.py")
    # d0 = 2 * 16 = 32; cross 1 layer: 2*32; MLP 32->3: 2*96; output (32+3): 2*35.
    assert m.dcn_forward_flops(_cfg(8)) == 64 + 192 + 70
    criteo = harness.load_json(harness.BENCH / "configs" / "criteo-dcn-alpt8.json")
    d0 = 39 * 16
    want = 2 * d0 * 5 + 2 * (d0 * 1000 + 4 * 1000 * 1000) + 2 * (d0 + 1000)
    assert m.dcn_forward_flops(criteo) == want


class _Red:
    def __init__(self, ops, spans, busy=1.0, window=2.0):
        self.ops, self.spans, self.busy_s, self.window_s = ops, spans, busy, window

    def ops_matching(self, pattern):
        import re
        keys = [k for k in self.ops if re.search(pattern, k)]
        return sum(self.ops[k] for k in keys), len(keys)

    def span_busy(self, name):
        return [s for n, s in self.spans if n == name]


class _Run:
    def __init__(self, red, counts, cfg):
        self.reduced, self.counts, self.config = red, counts, cfg
        self.peaks = {"hbm_bytes_per_s": 1000.0, "bf16_flops": 1e6}


def test_roofline_reader_from_counts():
    m = harness.load_module(LM / "dequant_gather_roofline.train.py")
    red = _Red({"dequant_gather": 0.5, "fusion.1": 9.0}, [("bench.train_step", (1, 1))] * 2)
    run = _Run(red, {"lookups_per_step": 4, "chips": 1}, _cfg(8))
    # 2 steps x 4 ids x 88 B = 704 B at 1000 B/s = 0.704 s least, over 0.5 s measured.
    assert abs(m.read(run) - 100 * 0.704 / 0.5) < 1e-9


def test_train_mfu_over_device_time():
    m = harness.load_module(LM / "train_mfu.py")
    red = _Red({"fusion.1": 1.0}, [], busy=0.5, window=2.0)
    run = _Run(red, {"samples": 10, "window_s": 2.0, "chips": 2}, _cfg(8))
    # 10 samples x 3 x 326 FLOPs over 0.5 s busy x 2 chips x 1e6 FLOP/s.
    assert abs(m.read(run) - 100 * 10 * 3 * 326 / (0.5 * 2 * 1e6)) < 1e-9


def test_readers_return_nothing_without_their_kernel():
    for name in ("dequant_gather_roofline.train", "sparse_row_update_roofline"):
        m = harness.load_module(LM / f"{name}.py")
        red = _Red({"fusion.1": 1.0}, [("bench.train_step", (1, 1))])
        assert m.read(_Run(red, {"lookups_per_step": 4, "chips": 1, "unique_ids": 3},
                           _cfg(8))) is None
        assert m.read(_Run(None, {}, _cfg(8))) is None
