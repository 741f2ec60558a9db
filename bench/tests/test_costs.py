"""Roofline byte counts of the hop kernels, at the cells' shapes, and the
readers of their roofline shares."""

from types import SimpleNamespace

import pytest

from harness import load_module, BENCH


def cost(name):
    return load_module(BENCH / "costs" / f"{name}.py")


def test_hop_adc_bytes_batch1024():
    # codes 1024·64·16 + LUTs 1024·16·256·4 + ids and out 2·1024·64·4
    assert cost("hop_adc").bytes_per_call(q=1024, r=64, m=16, k=256) == (
        1_048_576 + 16_777_216 + 524_288)


def test_hop_adc_fs_bytes_online16():
    # codes 16·64·24 + uint8 LUTs 16·48·16 + ids and out 2·16·64·4
    assert cost("hop_adc_fs").bytes_per_call(q=16, r=64, m=48, k=16) == (
        24_576 + 12_288 + 8_192)


def test_hop_adc_fs_bytes_batch1024():
    # codes 1024·64·24 + uint8 LUTs 1024·48·16 + ids and out 2·1024·64·4
    assert cost("hop_adc_fs").bytes_per_call(q=1024, r=64, m=48, k=16) == (
        1_572_864 + 786_432 + 524_288)


def test_odd_m_packs_up():
    assert cost("hop_adc_fs").bytes_per_call(q=1, r=1, m=3, k=16) == (
        2 + 3 * 16 + 8)


def test_ops_are_lookup_adds():
    assert cost("hop_adc").ops_per_call(q=2, r=3, m=4, k=256) == 24


@pytest.mark.parametrize("kernel", ["hop_adc", "hop_adc_fs"])
def test_roofline_reader(kernel):
    """Two calls of the kernel, each four times its least time: 25%. Only
    the reader's own kernel counts, and with no event it reads nothing."""
    import harness

    conf = {"quantizer": {"M": 4, "K": 16}, "index": {"R": 8}}
    traffic = {"batch": 2, "expand": 1}
    least_ns = cost(kernel).bytes_per_call(q=2, r=8, m=4, k=16)  # at 1 GB/s
    other = "hop_adc_fs" if kernel == "hop_adc" else "hop_adc"
    reader = load_module(BENCH / "metrics" / f"{kernel}_roofline.py")

    def ctx(events):
        trace = SimpleNamespace(events=events)
        return harness.layer_context([], trace, conf, traffic,
                                     {"hbm_bytes_per_s": 1e9},
                                     {"hop_adc", "hop_adc_fs"}, {})

    events = [(f"{kernel}.6", 0, 4 * least_ns), (f"{kernel}.6", 9e9,
                                                  4 * least_ns),
              (f"{other}.2", 0, 1e9)]
    assert reader.read(ctx(events)) == pytest.approx(25.0)
    assert reader.read(ctx(events[2:])) is None
