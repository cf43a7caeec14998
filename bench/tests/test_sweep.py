"""The sweep's placement seeds: a new one for every compile of a window,
drawn from ``--seed``, never one that set-up compiled."""

from bench.lib.compile_sweep import placement_seed


def test_placement_seeds_are_fresh_and_disjoint():
    for seed in (0, 7, 2**31 + 5):
        window = [placement_seed(seed, i) for i in range(500)]
        warm = [placement_seed(seed, i, warm=True) for i in range(500)]
        assert len(set(window)) == 500
        assert not set(window) & set(warm)
        assert all(0 <= s < 2**31 for s in window + warm)
    assert placement_seed(7, 0) != placement_seed(8, 0)
