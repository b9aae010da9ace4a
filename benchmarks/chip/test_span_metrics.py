"""CPU checks of the readers of the program's boundary samples
(``pytest benchmarks/chip``): each reader on a hand-made ``RunView``, and
``None`` where the samples hold nothing to read (an empty window, or a
program whose samples lack the fields)."""
from __future__ import annotations

import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import run  # noqa: E402

READERS = ("fetch_ms_per_boundary", "fetch_useful_share",
           "upload_useful_share", "frontend_ms_per_boundary")

SAMPLES = [
    {"active_frac": 1.0, "snapshot_fetch_s": 0.300, "fetch_rows": 4000,
     "fetch_rows_kept": 30, "upload_frames": 250, "upload_frame_slots": 1000,
     "client_pump_s": 0.001, "delivery_pump_s": 0.002},
    {"active_frac": 1.0, "snapshot_fetch_s": 0.100, "fetch_rows": 4000,
     "fetch_rows_kept": 10, "upload_frames": 0, "upload_frame_slots": 0,
     "client_pump_s": 0.003, "delivery_pump_s": 0.000},
]


def view(samples):
    return run.RunView(trace=None, timeseries=samples, frames_per_chunk=1,
                       ops_per_frame=1.0, peaks=None)


@pytest.mark.parametrize("name,want", [
    ("fetch_ms_per_boundary", 200.0),          # (300 + 100) / 2 ms
    ("fetch_useful_share", 0.5),               # 40 / 8000 rows
    ("upload_useful_share", 25.0),             # 250 / 1000 slots
    ("frontend_ms_per_boundary", 3.0),         # (3 + 3) / 2 ms
])
def test_reader_on_hand_made_samples(name, want):
    assert run.load_reader(name)(view(SAMPLES)) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_on_an_empty_window(name):
    assert run.load_reader(name)(view([])) is None


@pytest.mark.parametrize("name", READERS)
def test_reader_is_none_without_the_fields(name):
    """A program that does not write these fields (the samples carry only
    the fields the pool had before them) gives no reading, not an error."""
    assert run.load_reader(name)(view([{"active_frac": 1.0}] * 3)) is None
