"""The Shampoo statistics cell: its configuration's blocks are Shampoo's
blocking of the chip's share of MiniCPM-SALA's MLPs, and the rows-side
kernel's roofline reader reads only that kernel's device time."""
import importlib
import json

import pytest

from bench import work
from bench.cell import Reading, Run, Window, load_module, reader_of, resolve
from bench.tests.toy import REPO
from bench.trace_reduce import Event, Trace

CELL = "shampoo-sala-mlp.stats"


def test_blocks_are_shampoos_blocking_of_the_share():
    cfg = json.loads((REPO / "bench/configs/shampoo-sala-mlp.json")
                     .read_text())
    plan_of = importlib.import_module("repro.optim.shampoo")._plan
    share = cfg["share"]
    assert cfg["hidden_size"] == 4096 and cfg["intermediate_size"] == 16384
    assert share["chips"] * share["mlps_a_chip"] == share["mlps"] \
        == cfg["num_hidden_layers"]
    blocks = []
    for _ in range(cfg["layers"]):
        for shape in share["weights"].values():
            nbm, bsm, nbn, bsn = plan_of(tuple(shape),
                                         cfg["max_preconditioner_dim"], 64)
            blocks += [[bsm, bsn]] * (nbm * nbn)
    assert cfg["blocks"] == blocks and len(blocks) == 24


def _reading(events, extra):
    run = Run(resolve(REPO, CELL), 1, [None])
    win = Window(1.0, {}, 1, 0, calls=1, extra=extra)
    return Reading(run, win, Trace(devices={0: events}), [],
                   work.peaks_of("TPU v5 lite"), lo=0, hi=10 ** 9)


def test_rows_kernel_roofline_reads_the_rows_kernel_alone():
    reader = load_module(reader_of(REPO / "bench",
                                   "kernel_roofline.shampoo_rows"),
                         "check_kernel_roofline_shampoo_rows")
    flop = 2 * 197e12 * 0.01          # 10 ms at the peak
    extra = {"l_flop": flop, "l_bytes": 1e6}
    rows = "fused_rank_k_rows_l3_strassen_strassen_pd2.3"
    events = [Event(rows, 0, 50_000_000),                     # 50 ms
              Event(rows, 900_000_000, 1_100_000_000),        # 100 in it
              Event("fused_rank_k_l3_strassen_strassen_pd2.3",
                    100_000_000, 500_000_000)]
    assert reader.read(_reading(events, extra)) == pytest.approx(
        100 * 0.02 / 0.15)
    assert reader.read(_reading(events[2:], extra)) is None
    assert reader.read(_reading(events, {})) is None
