"""A traced tiny training run on the flash kernel path reports the share
of the dense grid the kernels launch (on the CPU this checks the wiring:
the kernels run in interpret mode)."""
from bench.tests import helpers


def test_traced_run_reports_flash_grid_share(monkeypatch, tmp_path):
    from bench import peaks
    # the CPU has no published peak; a stand-in row lets the readers run
    monkeypatch.setitem(peaks.PEAKS, "cpu", dict(peaks.PEAKS["TPU v5 lite"]))
    # rows of two 256-token blocks: the causal walk launches 3 of 4 tiles
    cell = helpers.tiny_train_cell(seq_len=512)
    cell.config = dict(cell.config, attn_impl="flash")
    res = helpers.drive(cell, tmp=tmp_path, trace=True)
    share = res["metrics"]["train.flash_grid_share"]["value"]
    assert 0 < share < 100
    assert share == 75.0
