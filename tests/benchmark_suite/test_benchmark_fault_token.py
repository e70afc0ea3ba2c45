"""The timed path broken underneath: a token altered where it is produced
has to come out as not correct."""
from benchmark_drive import drive, tiny_root


def test_a_token_altered_where_it_is_produced_is_not_correct(tmp_path,
                                                             monkeypatch):
    from deeplearning4j_tpu.serving import decode
    calls = {"n": 0}
    real = decode.TransformerAdapter._greedy

    def altered(logits):
        calls["n"] += 1
        tok = real(logits)
        return (tok + 1) % 128 if calls["n"] % 5 == 0 else tok

    monkeypatch.setattr(decode.TransformerAdapter, "_greedy",
                        staticmethod(altered))
    with tiny_root(tmp_path, monkeypatch) as man:
        r = drive(man, "tiny.closed", 8, 1.5, False)
    assert r["correct"] is False
    assert r["compared"]["served_gap"]["value"] > \
        r["compared"]["served_gap"]["limit"]
