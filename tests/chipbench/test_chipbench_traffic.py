"""The traffic generator: seeded, the same work for every seed, the
paper's Table 1 mix within sampling error."""
import numpy as np
import pytest

from harness import spec, traffic

HOL = spec.traffic("hol-mix")
HOL_CELL = spec.cell("qwen2-1.5b.hol-mix")


def test_same_seed_same_requests():
    a = traffic.open_loop(HOL, HOL_CELL, 60.0)
    ta = traffic.prompt_tokens(a, 151936, 2**31 + 5)
    tb = traffic.prompt_tokens(a, 151936, 2**31 + 5)
    assert all(np.array_equal(ta[k], tb[k]) for k in ta)


def test_seeds_replay_one_schedule():
    """Every seed sends the same sizes at the same times; the seed draws
    only the prompt tokens."""
    a = traffic.open_loop(HOL, HOL_CELL, 60.0)
    assert a == traffic.open_loop(HOL, HOL_CELL, 60.0)
    ta = traffic.prompt_tokens(a, 151936, 1)
    tb = traffic.prompt_tokens(a, 151936, 2)
    assert not all(np.array_equal(ta[k], tb[k]) for k in ta)


def test_table1_ratios_and_means():
    """Class shares and pre-grid prompt means match Table 1 within
    sampling error (20k draws)."""
    rng = np.random.default_rng(0)
    cell = dict(HOL_CELL, max_prompt=10**7, max_seq=10**7)
    sizes = traffic._draw_sizes(HOL, cell, 20000, rng)
    tasks = [t for t, _, _ in sizes]
    for name, c in HOL["classes"].items():
        share = tasks.count(name) / len(tasks)
        assert abs(share - c["ratio"]) < 4 * np.sqrt(c["ratio"] / len(tasks))
    # lognormal fit of each class, before the grid and the clip
    for name, c in HOL["classes"].items():
        mu, sig = traffic.lognormal_params(c["mean"], c["std"])
        x = np.random.default_rng(1).lognormal(mu, sig, 40000)
        assert abs(x.mean() - c["mean"]) < 0.05 * c["mean"]
        assert abs(x.std() - c["std"]) < 0.1 * c["std"]


def test_lengths_on_grid_and_within_limits():
    grid = traffic.prompt_grid(HOL, HOL_CELL)
    assert grid[0] == HOL["prompt"]["min"] and grid[-1] == HOL_CELL["max_prompt"]
    assert all(b / a <= 2.0 for a, b in zip(grid, grid[1:]))
    items = traffic.open_loop(HOL, HOL_CELL, 120.0)
    assert all(it.prompt_len in grid for it in items)
    o = HOL["output"]
    assert all(o["min"] <= it.output_len <= o["max"] for it in items)
    assert all(it.due < 120.0 for it in items)
    assert all(it.ttft_slo == HOL["classes"][it.task]["ttft_slo"]
               for it in items)


@pytest.mark.parametrize("n,expect", [(16, 16), (22, 16), (23, 32),
                                      (7000, 7680), (6000, 5792)])
def test_to_grid_nearest_in_log_space(n, expect):
    assert traffic.to_grid(n, traffic.prompt_grid(HOL, HOL_CELL)) == expect


def test_poisson_arrivals_at_the_cell_rate():
    """Gaps are exponential at the cell's rate: mean 1/rate and a
    coefficient of variation of 1, within sampling error (about 4k gaps)."""
    items = traffic.open_loop(HOL, HOL_CELL, 4000 / HOL_CELL["rate"])
    gaps = np.diff([0.0] + [it.due for it in items])
    assert len(gaps) > 3500
    mean = 1.0 / HOL_CELL["rate"]
    assert abs(gaps.mean() - mean) < 0.05 * mean
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.06
