"""`correct` comes out false for the control and for each fault the cells
can have, at smoke width, and true for the sound run beside them."""
import pytest

import faults


def test_sound_run_is_correct(smoke):
    out = smoke("health")
    assert out["correct"], out["checks"]


def test_control_bf16_parameters_is_not_correct(smoke):
    """The program's param_dtype=bfloat16 path, the precision below the
    configuration's float32 parameters: updates under half an ulp vanish."""
    out = smoke("health", control=True)
    assert not out["correct"]
    assert out["checks"]["change_gap"]["value"] > \
        out["checks"]["change_gap"]["limit"]


@pytest.mark.parametrize("fault,number,seq_len", [
    ("unchanged_state", "change_gap", 16),
    ("half_batch", "grad_gap", 16),
    ("altered_update", "change_gap", 16),
    ("altered_count", "count_diff", 16),
    ("altered_stats", "hist_moves", 16),
    ("altered_stats", "rms_gap", 16),
    ("half_tensor", "rms_gap", 16),
    ("half_tensor", "extreme_gap", 16),
    # bfloat16 sums lose the rows' partials once the running sum is some
    # hundreds of them: 512 rows, not the 32 of the usual smoke batch
    ("bf16_accum", "rms_gap", 256),
    ("planted_nan", "count_diff", 16),
])
def test_fault_is_not_correct(smoke, fault, number, seq_len):
    with faults.FAULTS[fault]():
        out = smoke("health", seq_len=seq_len)
    assert not out["correct"]
    c = out["checks"][number]
    assert c["value"] > c["limit"], out["checks"]


def test_stats_sums_are_compared_site_by_site():
    """The `sum` map's keys decode to (site, layer, field); each statistic
    is held to its own gap, and a missing key or a NaN count is a
    difference of counts."""
    import check
    names = ["block", "loss"]

    def key(site, layer, field):
        return ((site << 8) + layer) << 3 | field

    want = {}
    for field, v in enumerate((10, 1000, -3000, 3000, 3000, 0)):
        want[("block", 2, field)] = v
        want[("loss", 0, field)] = abs(v) if field == check.RMS else v
    have = {key(0, 2, f): v for (_, _, f), v in want.items()}
    have.update({key(1, 0, f): v for (s, _, f), v in want.items()
                 if s == "loss"})
    have[key(0, 2, check.RMS)] = 1010             # rms 1 % high
    have[key(0, 2, check.MAX)] = 2970             # max 1 % of absmax low
    have[key(1, 0, check.NONFINITE)] = 2          # two NaNs the ref lacks
    del have[key(1, 0, check.MEAN)]               # never written
    got = {"keys": list(have), "used": [1] * len(have),
           "values": list(have.values())}
    out = check.stats_numbers(got, want, names.__getitem__)
    assert out["rms_gap"] == pytest.approx(0.01)
    assert out["extreme_gap"] == pytest.approx(0.01)
    assert out["mean_gap"] == 0.0
    assert out["scalar_gap"] == pytest.approx(10 / 1000)
    assert out["count_diff"] == 2 + 1
