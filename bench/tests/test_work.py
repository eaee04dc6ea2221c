import pytest

from bench import work


def test_classical_tril_flop_of_the_paper_size():
    assert work.gram_flop(10000, 10000) == pytest.approx(1.0001e12, rel=0,
                                                        abs=1)
    assert work.gram_flop(4096, 16384) == 4096 * 16384 * 16385


def test_bytes_count_operand_result_and_state():
    # A read once, result written once, state read and written
    assert work.gram_bytes(10, 20, 4, 400) == 10 * 20 * 4 + 400 * 4
    assert work.gram_bytes(10, 20, 2, 0, state_bytes=840) == 400 + 2 * 840


def test_peaks_keyed_by_device_kind():
    p = work.peaks_of("TPU v5 lite")
    assert (p.flops, p.hbm_bytes_s) == (197e12, 819e9)
    assert "Google Cloud" in p.source
    with pytest.raises(KeyError, match="no published peaks"):
        work.peaks_of("TPU v9 imaginary")


@pytest.mark.parametrize("flop,nbytes,bound", [
    (1.0001e12, 8e8, "compute"), (1e9, 8e9, "memory")])
def test_least_time_takes_the_larger_term(flop, nbytes, bound):
    p = work.peaks_of("TPU v5 lite")
    least = work.least_time(flop, nbytes, p)
    assert least.bound == bound
    assert least.seconds == pytest.approx(max(flop / 197e12,
                                              nbytes / 819e9))
    assert work.least_time(flop, nbytes, p, chips=4).seconds == \
        pytest.approx(least.seconds / 4)


def test_roofline_share_reads_and_refuses_a_miscount():
    assert work.roofline_share(5e-3, 0.5) == pytest.approx(1.0)
    assert work.roofline_share(1.04, 1.0) == pytest.approx(104.0)
    with pytest.raises(ValueError, match="exceeds 105"):
        work.roofline_share(1.06, 1.0)
    with pytest.raises(ValueError, match="nothing ran"):
        work.roofline_share(1.0, 0.0)
