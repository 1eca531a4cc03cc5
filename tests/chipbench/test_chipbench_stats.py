"""End-to-end metric arithmetic."""
import math

import pytest

from harness import stats


def rec(due, first, finish=None, tpot=None, slo=1.0, tbt=0.1, out=4,
        prompt=100):
    return stats.Record(due=due, prompt_len=prompt, output_len=out,
                        ttft_slo=slo, tbt_slo=tbt, first_token=first,
                        finish=finish, mean_tpot=tpot)


def test_unfinished_is_inf_in_tail_and_a_miss():
    recs = [rec(0.0, 0.5 + 0.01 * i, 1.0, 0.05) for i in range(19)]
    recs.append(rec(0.0, None))
    assert not recs[-1].met and math.isinf(recs[-1].ttft)
    assert stats.goodput_rps(recs, 10.0) == pytest.approx(1.9)
    assert math.isinf(stats.ttft_slo_frac(recs, 100.0))
    assert stats.ttft_slo_frac(recs, 90.0) < 1.0
    # with a fifth unfinished the 90th percentile lands in the inf tail
    assert math.isinf(stats.ttft_slo_frac(recs[:8] + recs[-1:] * 2, 90.0))


def test_met_needs_both_limits():
    assert rec(0.0, 0.9, 2.0, 0.1).met
    assert not rec(0.0, 1.1, 2.0, 0.05).met          # TTFT over its limit
    assert not rec(0.0, 0.5, 2.0, 0.11).met          # TBT over its limit
    assert not rec(0.0, 0.5, None, None).met         # never finished
    assert rec(0.0, 0.5, None, None, out=0).met      # prefill-only


def test_ttft_normalised_by_class_limit():
    recs = [rec(1.0, 1.1, slo=0.25), rec(2.0, 4.0, slo=4.0)]
    assert stats.ttft_slo_frac(recs, 100.0) == pytest.approx(0.5)


def test_tbt_percentile():
    recs = [rec(0.0, 0.2, 1.0, t / 1e3) for t in range(1, 11)]
    assert stats.tbt_ms(recs, 90.0) == pytest.approx(9.1)
    # prefill-only and unfinished requests have no time per output token
    recs += [rec(0.0, 0.2, 1.0, None, out=0), rec(0.0, None)]
    assert stats.tbt_ms(recs, 90.0) == pytest.approx(9.1)


def test_lateness():
    assert stats.lateness([1.0, 2.0], [1.001, 2.5]) == \
        pytest.approx([0.001, 0.5])
