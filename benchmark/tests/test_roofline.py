"""The shapes-only count of a fleet pass, on shapes worked by hand."""

from benchmark.roofline import fleet_pass_count, least_seconds


def test_count_by_hand():
    # B=10 bindings, C=4 clusters, R=2 dims, P=3 profiles, K_PREV=2
    c = fleet_pass_count(10, 4, 2, 3, 2)
    # grid 40 + cluster table 4*2*8=64 + profiles 3*2*8=48
    # + rows 10*(12+1+16)=290, written 10*8=80
    assert c["bytes"] == 40 + 64 + 48 + 290 + 80
    assert c["int_ops"] == 10 * 4 * 4


def test_the_cell_is_bytes_bound_and_small():
    # rebalance-100kx100: grid 10 MB + rows 100k * 77 B + 0.8 MB written
    c = fleet_pass_count(100_000, 100, 3, 8, 8)
    assert c["bytes"] == 10_000_000 + 100 * 24 + 8 * 24 + 7_700_000 + 800_000
    peak = {"hbm_bytes_per_s": 819e9, "int8_ops_per_s": 393e12}
    t, bound = least_seconds(c, peak)
    assert bound == "bytes"
    assert 2.2e-5 < t < 2.3e-5
