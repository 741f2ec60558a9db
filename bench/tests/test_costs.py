"""Roofline byte counts of the hop kernels, at the cells' shapes."""

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


def test_odd_m_packs_up():
    assert cost("hop_adc_fs").bytes_per_call(q=1, r=1, m=3, k=16) == (
        2 + 3 * 16 + 8)


def test_ops_are_lookup_adds():
    assert cost("hop_adc").ops_per_call(q=2, r=3, m=4, k=256) == 24
