"""The timed path broken underneath: a train step that returns its state
unchanged has to come out as not correct."""
from benchmark_drive import drive, tiny_root


def test_a_step_that_returns_its_state_unchanged_is_not_correct(
        tmp_path, monkeypatch):
    from benchmark.models import resnet50_zoo

    real_build = resnet50_zoo.build

    def broken_build(cfg, seed, chips=1):
        import jax
        net, fit = real_build(cfg, seed, chips)
        raw = jax.jit(net._train_step_raw)      # the same step, no donation

        def unchanged(params, opt, state, it, rng, *batch):
            out = raw(params, opt, state, it, rng, *batch)
            return (params, opt, state) + tuple(out[3:])
        # the real step runs and its loss is kept; its new state is dropped
        net._train_step_fn = unchanged
        return net, fit

    monkeypatch.setattr(resnet50_zoo, "build", broken_build)
    with tiny_root(tmp_path, monkeypatch) as man:
        r = drive(man, "tiny.fit", 5, 1.0, False)
    assert r["correct"] is False
    assert r["compared"]["change_gap_median"]["value"] == 1.0
    assert r["compared"]["bn_state_gap"]["value"] == 1.0
    assert r["compared"]["grad_gap_median"]["value"] > \
        r["compared"]["grad_gap_median"]["limit"]
