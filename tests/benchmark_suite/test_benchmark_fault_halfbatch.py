"""The timed path broken underneath: half of every batch left out, the
mean taken over the rest, has to come out as not correct."""
from benchmark_drive import drive, tiny_root


def test_half_of_the_batch_left_out_is_not_correct(tmp_path, monkeypatch):
    from benchmark.kinds import fit_ring
    from deeplearning4j_tpu.data.dataset import DataSet

    real_next = fit_ring.RingIterator.__next__

    def half(self):
        ds = real_next(self)
        n = ds.num_examples() // 2
        return DataSet(ds.features[:n], ds.labels[:n])

    monkeypatch.setattr(fit_ring.RingIterator, "__next__", half)
    with tiny_root(tmp_path, monkeypatch) as man:
        r = drive(man, "tiny.fit", 6, 1.0, False)
    assert r["correct"] is False
    c = r["compared"]
    assert any(c[k]["value"] > c[k]["limit"]
               for k in ("loss1_gap", "grad_gap_median", "change_gap_median"))
