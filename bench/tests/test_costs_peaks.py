"""The edge-relax byte count against hand counts, and the peak table."""
import pytest

from bench.costs import edge_relax_bytes
from bench.peaks import peak


def test_edge_relax_bytes_one_block():
    # one 512-edge block into one 256-node tile: nine int32 slabs of 512
    # (18,432 B), three int32 planes of 256 (3,072 B), the 1-entry
    # block-to-tile map and delta (8 B)
    assert edge_relax_bytes(1, 512, 1, 256) == 18432 + 3072 + 8


def test_edge_relax_bytes_counts_every_block_and_tile():
    # 10 blocks of 128 edges, 3 tiles of 64 nodes:
    # 9*10*128*4 = 46,080; 3*3*64*4 = 2,304; (10 + 1)*4 = 44
    assert edge_relax_bytes(10, 128, 3, 64) == 46080 + 2304 + 44


def test_v5e_peaks():
    assert peak("TPU v5 lite", "hbm_bytes_per_s") == 819e9
    assert peak("TPU v5 lite", "bf16_flops_per_s") == 197e12


def test_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        peak("TPU v9 imaginary", "hbm_bytes_per_s")
